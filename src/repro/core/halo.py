"""Halo-exchange spatial partitioning for CNN fused groups — the LITERAL
mapping of the paper's fused-layer dataflow onto a device mesh.

Feature maps are sharded along the H (row) dimension across the ``model``
axis.  A fused group of conv layers needs, per device, only the
RECEPTIVE-FIELD HALO rows of its neighbours — exchanged ONCE per fused
group with a pair of ``jax.lax.ppermute`` shifts (the TPU analogue of the
paper's one-time cross-bank halo transfer, Fig. 1b ②), after which every
layer of the group runs device-local.  Compare with the layer-by-layer
mapping, which would re-gather the full activation map between layers.

``run_fused_group`` wraps a group function in ``shard_map``; halo validity
is guaranteed by exchanging ``halo`` rows where ``halo`` ≥ the group's
receptive-field growth (computed exactly by ``repro.core.tiling``), and
recomputing edge rows locally (the paper's redundant-compute trade).

GLOBAL-BOUNDARY SEMANTICS: ``run_fused_group`` (single opaque group fn) is
exact on every INTERIOR shard; the two global-boundary shards deviate
within the group's receptive field because out-of-image halo rows pick up
real data through kernel overlap instead of staying equal to conv padding.
``run_fused_group_exact`` takes the group as a LIST of per-layer functions
and re-zeroes out-of-image rows after every layer (the masking used by
production spatial partitioning) — exact everywhere, for stride-1
same-padded layers.  ``tests/test_policies_sharded.py`` covers both.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def group_halo_rows(group_graph, tiles: int) -> int:
    """Exact halo rows a fused group needs: max over tiles of the extra
    input rows beyond the tile's own shard (from the tiling engine)."""
    from repro.core.tiling import tile_group
    t = tile_group(group_graph, tiles, 1)
    first = group_graph[0]
    own = first.iy // tiles
    halo = 0
    for i in range(t.num_tiles):
        lo, hi = t.input_req[i].y
        halo = max(halo, (hi - lo) - own)
    return halo


def exchange_halo(x: jnp.ndarray, halo_up: int, halo_down: int,
                  axis_name: str) -> jnp.ndarray:
    """x: (B, H_shard, W, C) on each device.  Returns x extended with
    ``halo_up`` rows from the previous device and ``halo_down`` rows from
    the next (zero rows at the boundary devices — conv padding semantics).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    parts = []
    if halo_up:
        # rows flowing DOWNWARD: device i sends its last rows to i+1
        send_down = [(i, (i + 1) % n) for i in range(n)]
        top = jax.lax.ppermute(x[:, -halo_up:], axis_name, send_down)
        top = jnp.where(idx == 0, jnp.zeros_like(top), top)
        parts.append(top)
    parts.append(x)
    if halo_down:
        send_up = [(i, (i - 1) % n) for i in range(n)]
        bot = jax.lax.ppermute(x[:, :halo_down], axis_name, send_up)
        bot = jnp.where(idx == n - 1, jnp.zeros_like(bot), bot)
        parts.append(bot)
    return jnp.concatenate(parts, axis=1)


def _crop_valid(y: jnp.ndarray, crop_up: int, crop_down: int) -> jnp.ndarray:
    if crop_down:
        return y[:, crop_up:-crop_down]
    return y[:, crop_up:]


def run_fused_group(group_fn: Callable[[jnp.ndarray], jnp.ndarray],
                    x: jnp.ndarray, mesh: Mesh, *, halo: int,
                    shrink: int, axis: str = "model") -> jnp.ndarray:
    """Execute ``group_fn`` under row-sharded ``shard_map`` with a single
    up-front halo exchange.

    ``halo``   — input rows needed from each neighbour (receptive field);
    ``shrink`` — output rows produced by the halo that belong to the
                 neighbour's shard (cropped after the group runs; this is
                 the redundant edge compute).  For stride-s groups,
                 shrink = halo // s.
    """

    def local(xs: jnp.ndarray) -> jnp.ndarray:
        ext = exchange_halo(xs, halo, halo, axis)
        y = group_fn(ext)
        return _crop_valid(y, shrink, shrink)

    spec_in = P(None, axis, None, None)
    return shard_map(local, mesh=mesh, in_specs=(spec_in,),
                     out_specs=spec_in)(x)


def run_fused_group_exact(layer_fns, x: jnp.ndarray, mesh: Mesh, *,
                          halo: int, axis: str = "model") -> jnp.ndarray:
    """Exact everywhere: one halo exchange for the whole fused group, then
    per-layer edge MASKING so out-of-image rows equal conv-padding zeros at
    every layer (stride-1 same-padded groups).  This is the paper's fused
    dataflow with boundary-tile interval clipping (tiling.py semantics) in
    mesh form."""
    H = x.shape[1]

    def local(xs: jnp.ndarray) -> jnp.ndarray:
        n = jax.lax.axis_size(axis)
        idx = jax.lax.axis_index(axis)
        shard = H // n
        ext = exchange_halo(xs, halo, halo, axis)
        # global positions of extended rows
        pos = jnp.arange(ext.shape[1]) + idx * shard - halo
        valid = ((pos >= 0) & (pos < H))[None, :, None, None]
        y = ext
        for fn in layer_fns:
            y = fn(y) * valid.astype(ext.dtype)
        return y[:, halo:-halo] if halo else y

    spec_in = P(None, axis, None, None)
    return shard_map(local, mesh=mesh, in_specs=(spec_in,),
                     out_specs=spec_in)(x)
