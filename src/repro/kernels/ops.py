"""Jit'd public wrappers around the Pallas kernels.

``fused_conv`` takes ``interpret`` from the caller and defaults to the
compiled TPU kernel; CPU callers (tests, examples) pass ``interpret=True``.
The sequence-model kernels pick interpret mode whenever the backend
is not a TPU.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.fused_conv import fused_conv_kernel
from repro.kernels.mamba_scan import mamba_scan_kernel
from repro.kernels.mlstm_scan import mlstm_scan_kernel


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128):
    """(B, S, H, hd) × (B, T, KV, hd)² → (B, S, H, hd)."""
    Bt, S, H, D = q.shape
    _, T, KV, _ = k.shape
    out = flash_attention_kernel(
        q.transpose(0, 2, 1, 3).reshape(Bt * H, S, D),
        k.transpose(0, 2, 1, 3).reshape(Bt * KV, T, D),
        v.transpose(0, 2, 1, 3).reshape(Bt * KV, T, D),
        causal=causal, window=window, softcap=softcap,
        block_q=min(block_q, S), block_k=min(block_k, T),
        interpret=_auto_interpret())
    return out.reshape(Bt, H, S, D).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("stride", "padding", "relu",
                                             "tile_h", "cout_block",
                                             "interpret"))
def fused_conv(x, w, scale, shift, *, stride=1, padding=1, relu=True,
               residual=None, tile_h=8, cout_block=128, interpret=False):
    return fused_conv_kernel(x, w, scale, shift, stride=stride,
                             padding=padding, relu=relu, residual=residual,
                             tile_h=tile_h, cout_block=cout_block,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk",))
def mamba_scan(dtx, a_log, Bm, Cm, *, chunk=128):
    return mamba_scan_kernel(dtx, a_log, Bm, Cm, chunk=chunk,
                             interpret=_auto_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def mlstm_scan(q, k, v, i_pre, f_pre, *, chunk=64):
    return mlstm_scan_kernel(q, k, v, i_pre, f_pre, chunk=chunk,
                             interpret=_auto_interpret())
