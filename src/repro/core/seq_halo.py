"""Windowed-halo attention: the paper's conv-halo transplanted to
sliding-window attention (gemma2's local layers) under sequence sharding.

A local-attention layer with window W needs, per sequence shard of length
S_shard, only the last W−1 positions of the PRECEDING shards — a 1-D halo,
exactly the paper's Fig. 1(b) receptive-field rows.  Instead of the full
K/V all-gather GSPMD emits for sequence-sharded attention, each device
pulls ``h = ⌈(W−1)/S_shard⌉`` neighbour shards of K/V with ``h`` ring
``ppermute`` steps and computes masked attention locally:

    collective bytes:  all-gather  = (n−1)/n · |KV|
                       halo        = h/n · |KV|        (h ≪ n)

For gemma2 @ prefill_32k on a 16-way axis (S_shard = 2048, W = 4096 ⇒
h = 2): 2/15 of the gather traffic ≈ 7.5× less.  Exactness: causal +
window masking is applied inside the shard against global positions, so
the result equals the monolithic windowed attention bit-for-bit (same
einsum order).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.layers import attention_scores


def _ring_halo(x: jnp.ndarray, steps: int, axis: str) -> jnp.ndarray:
    """Collect ``steps`` predecessor shards of x (B, S_shard, KV, hd) via
    ring ppermute; returns (B, (steps+1)·S_shard, KV, hd) where the last
    S_shard rows are the local shard and earlier rows are predecessors
    (zeros beyond the sequence start)."""
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    parts = [x]
    cur = x
    for s in range(1, steps + 1):
        # shift by one each time: device i receives from i-1
        perm = [(i, (i + 1) % n) for i in range(n)]
        cur = jax.lax.ppermute(cur, axis, perm)
        valid = idx >= s                     # device s-1 wraps → mask
        cur = jnp.where(valid, cur, jnp.zeros_like(cur))
        parts.append(cur)
    # parts[k] holds the shard from k devices back; order chronologically
    return jnp.concatenate(parts[::-1], axis=1)


def windowed_attention_halo(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            *, window: int, mesh: Mesh,
                            axis: str = "model",
                            softcap: float = 0.0) -> jnp.ndarray:
    """q/k/v: (B, S, H|KV, hd) sequence-sharded on ``axis``.  Causal
    sliding-window attention with halo K/V exchange instead of all-gather.
    """
    S = q.shape[1]
    n = mesh.shape[axis]
    s_shard = S // n
    halo_steps = min(n - 1, math.ceil(max(window - 1, 0) / s_shard))

    def local(qs, ks, vs):
        idx = jax.lax.axis_index(axis)
        k_ext = _ring_halo(ks, halo_steps, axis)
        v_ext = _ring_halo(vs, halo_steps, axis)
        T = k_ext.shape[1]
        # global positions
        q_pos = idx * s_shard + jnp.arange(s_shard)
        k_pos = (idx - halo_steps) * s_shard + jnp.arange(T)
        m = (k_pos[None, :] <= q_pos[:, None]) \
            & (k_pos[None, :] > q_pos[:, None] - window) \
            & (k_pos[None, :] >= 0)
        return attention_scores(qs, k_ext, v_ext, m[None], softcap)

    spec = P(None, axis, None, None)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def halo_vs_gather_bytes(S: int, kv_heads: int, head_dim: int, *,
                         window: int, n_shards: int,
                         dtype_bytes: int = 2) -> dict:
    """Napkin model used in EXPERIMENTS.md: per-device K/V collective bytes
    for all-gather vs windowed halo."""
    s_shard = S // n_shards
    kv_bytes = 2 * S * kv_heads * head_dim * dtype_bytes  # K and V
    halo_steps = min(n_shards - 1,
                     math.ceil(max(window - 1, 0) / s_shard))
    return {
        "all_gather": kv_bytes * (n_shards - 1) / n_shards,
        "halo": kv_bytes * halo_steps / n_shards,
        "ratio": (n_shards - 1) / max(halo_steps, 1),
    }
