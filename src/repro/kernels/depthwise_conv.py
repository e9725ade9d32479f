"""Depthwise k×k conv, stride 1, same padding, with its bias: a Pallas TPU
kernel that reads each input row from HBM once per band.

XLA's depthwise emitters tile the output into small windows and fetch
each window's input with its halo, so neighbouring windows re-read the
same rows (ConvNeXt-T's 7×7 convs fetch 8.5–10× their input).  Here a
band of ``rows`` output rows over the full width stays in VMEM with its
k − 1 halo rows, so the input is read at most (rows + k − 1) ÷ rows
times.  Neighbouring bands' windows overlap, as ``fused_conv``'s do, but
the first and last band's windows reach past the map, and Mosaic's
element windows take no low padding; so the kernel DMAs each band's rows
itself into a VMEM buffer whose border is the conv's zero padding,
double-buffered: the next band's rows arrive while this band computes.

Layout: the map is laid out spatial-major, (H, W, ·, ·), so that every
tap is an offset along a major dimension (a choice of vreg, no shift);
batch and channels share the (sublane, lane) tile, in the order that pads
that tile least (``tile_plan``).  At a batch of 128 and 96 or 192
channels the batch fills the lanes; at 384 or 768 the channels do: the
layouts XLA keeps these maps in, so the transposes around the call are
bitcasts.  The weights and bias come broadcast to the tile, one vreg per
tap, and the k·k taps run as float32 multiply-adds on the VPU over a
strip of up to 8 output pixels held in vregs.  With the batch in lanes
the channels are the innermost grid axis, and the kernel also sums each
output pixel over the channels (a LayerNorm's mean) as it goes.

Unlike ``fused_conv``'s, the call's operands and outputs stay where XLA
places them: pinned to HBM, XLA copied the outputs of 28×28 and 14×14
maps back into VMEM after each call (PERF.md, Findings).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES, LANES = 8, 128
# VMEM for the double-buffered input window and output band of one step
VMEM_BUDGET = 12 << 20
STRIP = 8               # output pixels per accumulator strip, at most


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


class TilePlan(NamedTuple):
    rows: int               # output rows per band; divides the height
    batch_in_lanes: bool    # tile (channels, batch); else (batch, channels)
    channel_block: int      # a block of the lane dimension is 128 wide,
    batch_block: int        # over that dimension padded to whole lanes
    read_factor: float      # input rows DMA'd from HBM ÷ rows of the map


def batch_in_lanes(n: int, c: int) -> bool:
    """Whether an (n, ·, ·, c) map pads its (sublane, lane) tile less with
    the batch in lanes than with the channels (channels on a tie): the
    layout XLA keeps ConvNeXt-T's stage 1-2 maps in at a batch of 128."""
    return (_round_up(c, SUBLANES) * _round_up(n, LANES)
            < _round_up(n, SUBLANES) * _round_up(c, LANES))


def tile_plan(n: int, h: int, w: int, c: int, k: int) -> TilePlan:
    """The kernel's tiling of an (n, h, w, c) map for a k×k depthwise conv:
    the (sublane, lane) order of batch and channels that pads the tile
    least (channels in lanes on a tie), a vreg's 8 sublanes where the size
    allows, 128 lanes, and the most rows per band whose buffers fit
    ``VMEM_BUDGET``.  A band has at least k // 2 rows, unless it is the
    whole map, so only the first and last band reach past it."""
    lanes_batch = batch_in_lanes(n, c)
    sub = c if lanes_batch else n
    block = SUBLANES if sub % SUBLANES == 0 else sub
    tile = _round_up(block, SUBLANES) * LANES * 4
    cb, nb = (block, LANES) if lanes_batch else (LANES, block)
    halo = k - 1
    fits = [d for d in range(1, h + 1)
            if h % d == 0 and (d == h or d >= k // 2)]
    rows = max([d for d in fits
                if 2 * ((d + halo) * (w + halo) + d * w) * tile
                <= VMEM_BUDGET], default=min(fits))
    return TilePlan(rows, lanes_batch, cb, nb,
                    (h + halo * (h // rows - 1)) / h)


def _kernel(x_ref, w_ref, *refs, k: int, rows: int, height: int,
            width: int, strip: int, channel_sum: bool):
    if channel_sum:
        o_ref, sum_ref, buf, sem, part = refs
    else:
        o_ref, buf, sem = refs
    p = k // 2
    a, bb = buf.shape[3:]
    bands = height // rows
    t, i = pl.program_id(1), pl.program_id(2)
    ni = pl.num_programs(2)
    step = (pl.program_id(0) * bands + t) * ni + i
    steps = pl.num_programs(0) * bands * ni

    def copies(s, slot):
        """(condition, DMA) for each row range grid step ``s``'s band may
        have: the rows of the map it reads, into ``buf[slot]``."""
        j, tt, ii = s // (bands * ni), s // ni % bands, s % ni

        def copy(src, dst, n):
            return pltpu.make_async_copy(
                x_ref.at[pl.ds(src, n), :, pl.ds(ii * a, a), pl.ds(j * bb, bb)],
                buf.at[slot, pl.ds(dst, n), pl.ds(p, width)], sem.at[slot])
        if bands == 1:
            return [(None, copy(0, p, height))]
        out = [(tt == 0, copy(0, p, rows + p)),
               (tt == bands - 1, copy(height - rows - p, 0, rows + p))]
        if bands > 2:
            out.append(((tt > 0) & (tt < bands - 1),
                        copy(tt * rows - p, 0, rows + 2 * p)))
        return out

    def each(s, slot, method):
        for cond, dma in copies(s, slot):
            if cond is None:
                getattr(dma, method)()
            else:
                pl.when(cond)(getattr(dma, method))

    slot = step % 2

    @pl.when(step == 0)
    def _():
        each(step, slot, "start")

    @pl.when(step + 1 < steps)
    def _():
        each(step + 1, 1 - slot, "start")

    each(step, slot, "wait")

    # the border no DMA writes is the conv's zero padding
    tile = buf.shape[3:]
    buf[slot, :, :p] = jnp.zeros((rows + 2 * p, p) + tile, buf.dtype)
    buf[slot, :, width + p:] = jnp.zeros((rows + 2 * p, p) + tile, buf.dtype)
    edge = jnp.zeros((p, width + 2 * p) + tile, buf.dtype)

    @pl.when(t == 0)
    def _():
        buf[slot, :p] = edge

    @pl.when(t == bands - 1)
    def _():
        buf[slot, rows + p:] = edge

    if channel_sum:
        @pl.when(i == 0)
        def _():
            part[...] = jnp.zeros(part.shape, part.dtype)

    bias = w_ref[k * k].astype(jnp.float32)

    def row(r, carry):
        for s in range(0, width, strip):
            acc = jnp.broadcast_to(bias, (strip,) + tile)
            for dr in range(k):
                xs = buf[slot, r + dr, s:s + strip + k - 1].astype(jnp.float32)
                for dc in range(k):
                    acc = acc + xs[dc:dc + strip] \
                        * w_ref[dr * k + dc].astype(jnp.float32)
            o_ref[r, s:s + strip] = acc.astype(o_ref.dtype)
            if channel_sum:
                part[r, s:s + strip] += acc
        return carry

    jax.lax.fori_loop(0, rows, row, 0)

    if channel_sum:     # the last block of channels: sum over sublanes
        @pl.when(i == ni - 1)
        def _():
            def total(r, carry):
                sum_ref[r] = jnp.sum(part[r], axis=1)
                return carry
            jax.lax.fori_loop(0, rows, total, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def depthwise_conv_kernel(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                          interpret: bool = False
                          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (N, H, W, C) NHWC; w: (k, k, 1, C), k odd; b: (C,).  Returns
    the (N, H, W, C) depthwise conv, stride 1, padding k // 2, plus b, and
    its (N, H, W) sum over channels in float32.  With the batch in lanes
    the kernel sums the channels as it goes (blocks of channels are the
    innermost grid axis); with the channels in lanes XLA sums them."""
    n, h, wd, c = x.shape
    k = w.shape[0]
    if w.shape != (k, k, 1, c) or k % 2 == 0:
        raise ValueError(f"need an odd k×k×1×{c} kernel, got {w.shape}")
    plan = tile_plan(n, h, wd, c, k)
    p = k // 2
    strip = max(d for d in range(1, min(STRIP, wd) + 1) if wd % d == 0)
    # the transposes below are bitcasts; behind the barrier XLA does not
    # fuse them into the map's producer, which would then write it twice
    x = jax.lax.optimization_barrier(x)
    # the k·k taps and the bias, each broadcast to the tile: one fusion,
    # which reads the weights in whatever layout they come
    taps = jnp.concatenate([w.reshape(k * k, c), b[None]])
    if plan.batch_in_lanes:     # tile (channels, batch)
        xt = x.transpose(1, 2, 3, 0)
        a, bb = plan.channel_block, plan.batch_block
        wt = jnp.broadcast_to(taps[:, :, None], (k * k + 1, c, bb))

        def tile_of(j, i):      # the weights' block for tile (i, j)
            return 0, i, 0
    else:                       # tile (batch, channels)
        xt = x.transpose(1, 2, 0, 3)
        a, bb = plan.batch_block, plan.channel_block
        wt = jnp.broadcast_to(taps[:, None], (k * k + 1, a, c))

        def tile_of(j, i):
            return 0, 0, j
    # Mosaic slices HBM along the lanes in whole tiles only: pad the lane
    # dimension to them (no ConvNeXt-T map at a batch of 128 needs it)
    lanes = xt.shape[3]
    if lanes % LANES:
        def pad(v):
            return jnp.pad(v, [(0, 0)] * (v.ndim - 1)
                           + [(0, -v.shape[-1] % LANES)])
        xt, wt = pad(xt), pad(wt)
    rows = plan.rows
    channel_sum = plan.batch_in_lanes
    grid = (xt.shape[3] // bb, h // rows, xt.shape[2] // a)
    out_specs = [pl.BlockSpec((rows, wd, a, bb), lambda j, t, i: (t, 0, i, j))]
    out_shape = [jax.ShapeDtypeStruct(xt.shape, x.dtype)]
    scratch = [pltpu.VMEM((2, rows + 2 * p, wd + 2 * p, a, bb), x.dtype),
               pltpu.SemaphoreType.DMA((2,))]
    if channel_sum:
        out_specs.append(pl.BlockSpec((rows, wd, bb),
                                      lambda j, t, i: (t, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct((h, wd, xt.shape[3]),
                                              jnp.float32))
        scratch.append(pltpu.VMEM((rows, wd, a, bb), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_kernel, k=k, rows=rows, height=h, width=wd,
                          strip=strip, channel_sum=channel_sum),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((k * k + 1, a, bb), lambda j, t, i: tile_of(j, i)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        # the next step's rows are fetched during this one, and the
        # channel sum is carried across steps: keep the order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=2 * VMEM_BUDGET),
        name="depthwise_conv",
        interpret=interpret,
    )(xt, wt)
    if channel_sum:
        y, total = outs
        return y[..., :lanes].transpose(3, 0, 1, 2), \
            total[..., :lanes].transpose(2, 0, 1)
    y = outs[0][..., :lanes].transpose(2, 0, 1, 3)
    return y, jnp.sum(y.astype(jnp.float32), axis=-1)
