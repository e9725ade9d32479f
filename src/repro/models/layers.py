"""Core NN layers in pure JAX: norms, RoPE, GQA attention, gated MLPs,
embeddings, and the conv/bn/pool set for the CNNs.

Conventions:
* parameters are plain nested dicts of ``jnp.ndarray``;
* every layer is an ``init_*(key, ...) -> params`` / ``apply(params, x)``
  pair of pure functions;
* activations follow the config compute dtype; matmuls accumulate in f32
  via ``preferred_element_type`` where it matters.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(dim: int, dtype) -> jnp.ndarray:
    return jnp.ones((dim,), dtype)


def rmsnorm(w: jnp.ndarray, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * w


def init_layernorm(dim: int, dtype) -> Params:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(p: Params, x: jnp.ndarray, eps: float = 1e-5,
              total: jnp.ndarray | None = None) -> jnp.ndarray:
    """LayerNorm over the last axis; ``total``, x's float32 sum over that
    axis where its producer has it (``depthwise_conv``), gives the mean."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if total is None:
        mu = jnp.mean(x32, axis=-1, keepdims=True)
    else:
        mu = total[..., None] / x.shape[-1]
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * p["scale"] + p["bias"]


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    if theta <= 0:
        return x
    freqs = rope_frequencies(x.shape[-1], theta)          # (half,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (.., S, half)
    cos = jnp.cos(angles)[..., :, None, :]                # (.., S, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, sliding window, softcap, qk-norm)
# ---------------------------------------------------------------------------

def init_attention(key, cfg, dtype) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, \
        cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, kv * hd, dtype),
        "wv": dense_init(ks[2], d, kv * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def _softcap(logits: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap and cap > 0:
        return cap * jnp.tanh(logits / cap)
    return logits


def attention_scores(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     mask: jnp.ndarray | None, softcap: float = 0.0,
                     bias: jnp.ndarray | None = None) -> jnp.ndarray:
    """q: (B,S,H,hd)  k/v: (B,T,KV,hd) with H = KV*G.  mask: broadcastable
    to (B,H,S,T), True = attend; ``None`` attends everywhere.  bias: a
    float32 term (B or 1, H, S, T) added to the logits before the softmax
    (Swin's relative position bias and shift mask)."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    logits = _softcap(logits, softcap)
    if bias is not None:
        logits = logits + bias.reshape(bias.shape[0], KV, G, S, T)
    if mask is not None:
        m = mask.reshape(B, KV, G, S, T) if mask.ndim == 4 and mask.shape[1] == H \
            else mask[:, None, None, :, :] if mask.ndim == 3 else mask
        logits = jnp.where(m, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def causal_mask(S: int, T: int, q_offset: jnp.ndarray | int = 0,
                window: int = 0) -> jnp.ndarray:
    """(1, S, T) boolean mask: query i (global pos q_offset+i) attends to
    keys ≤ its position, within ``window`` if nonzero."""
    qpos = jnp.arange(S)[:, None] + q_offset
    kpos = jnp.arange(T)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m[None]


def attention(p: Params, x: jnp.ndarray, cfg, *, positions: jnp.ndarray,
              mask: jnp.ndarray, kv_override=None) -> jnp.ndarray:
    """Full attention block (projections + scores).  ``kv_override`` feeds
    cross-attention (keys/values from encoder states)."""
    from repro.core.hints import hint
    B, S, d = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = hint("qkv", (x @ p["wq"]).reshape(B, S, h, hd))
    if kv_override is None:
        k = hint("qkv", (x @ p["wk"]).reshape(B, S, kv, hd))
        v = hint("qkv", (x @ p["wv"]).reshape(B, S, kv, hd))
    else:
        src = kv_override
        k = (src @ p["wk"]).reshape(B, src.shape[1], kv, hd)
        v = (src @ p["wv"]).reshape(B, src.shape[1], kv, hd)
    if cfg.qk_norm:
        q = hint("qkv", rmsnorm(p["q_norm"], q, cfg.norm_eps))
        k = hint("qkv", rmsnorm(p["k_norm"], k, cfg.norm_eps))
    if kv_override is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    out = hint("attn_out", attention_scores(q, k, v, mask, cfg.attn_softcap))
    return out.reshape(B, S, h * hd) @ p["wo"]


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], d_model, d_ff, dtype),
        "w_up": dense_init(ks[1], d_model, d_ff, dtype),
        "w_down": dense_init(ks[2], d_ff, d_model, dtype),
    }


def mlp(p: Params, x: jnp.ndarray, activation: str = "silu") -> jnp.ndarray:
    act = jax.nn.silu if activation == "silu" else jax.nn.gelu
    return (act(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def gelu(x: jnp.ndarray) -> jnp.ndarray:
    """The exact GELU, x·Φ(x), through erf: ``jax.nn.gelu``'s erfc form
    lowers on a TPU to sign-mask fusions that carry no layer scope."""
    return 0.5 * x * (1.0 + jax.lax.erf(x * math.sqrt(0.5)))


# ---------------------------------------------------------------------------
# conv/bn/pool for the CNNs (ResNet, ConvNeXt)
# ---------------------------------------------------------------------------

def init_conv(key, kh: int, kw: int, cin: int, cout: int, dtype) -> jnp.ndarray:
    fan_in = kh * kw * cin
    return (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
            * math.sqrt(2.0 / fan_in)).astype(dtype)


# contraction depth of the TPU v5e matrix unit
MXU_DEPTH = 128


def space_to_depth(xp: jnp.ndarray, w: jnp.ndarray, s: int):
    """Stride-s conv over xp → stride-1 conv over the returned input.

    Phase (a, b) of xp (rows a::s, cols b::s) becomes a channel group and
    tap (r, c) moves to tap (r//s, c//s) of group (r%s, c%s).  Only phases
    some tap reads are kept (a 1×1/s conv keeps one).  xp's H and W must
    be multiples of s."""
    kh, kw, cin, cout = w.shape
    B, H, W, _ = xp.shape
    skh, skw = -(-kh // s), -(-kw // s)
    pa, pb = min(kh, s), min(kw, s)          # the phases some tap reads
    # reshapes, unit-stride slices and one transpose: XLA lowers strided
    # slices of the map to gathers
    xs = xp.reshape(B, H // s, s, W // s, s, cin)[:, :, :pa, :, :pb]
    xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(B, H // s, W // s, -1)
    ws = jnp.pad(w, ((0, skh * s - kh), (0, skw * s - kw), (0, 0), (0, 0))
                 ).reshape(skh, s, skw, s, cin, cout)[:, :pa, :, :pb]
    ws = ws.transpose(0, 2, 1, 3, 4, 5).reshape(skh, skw, -1, cout)
    return xs, ws


def conv2d(w: jnp.ndarray, x: jnp.ndarray, stride: int = 1,
           padding: int = 0, groups: int = 1) -> jnp.ndarray:
    """x: NHWC, w: HWIO, with I = Cin / groups (``groups = Cin``: a
    depthwise conv, w of shape (kh, kw, 1, Cin)).

    A depthwise conv (groups = Cin = Cout) with an odd square kernel,
    stride 1 and padding k // 2 is ``depthwise_conv``.

    A strided conv whose input has so few channels that stride² · Cin
    still fits the matrix unit's contraction (ResNet's 7×7/2 stem on 3
    channels) runs as a stride-1 conv over the space-to-depth input: a
    4×4 conv over 12 channels in place of a 7×7/2 over 3.  The kernel
    takes zero taps in front, so that the leading padding is whole
    space-to-depth pixels the conv pads itself, and behind, up to a
    multiple of the stride; the map is relaid out once, unpadded.  Same
    products; the zero taps add exact zeros.  Other grouped convs keep
    the plain form."""
    kh, kw, cin, cout = w.shape
    s, p = stride, padding
    if (groups == x.shape[-1] == cout and cin == 1 and s == 1 and kh == kw
            and kh % 2 and p == kh // 2):
        return depthwise_conv(w, x)[0]
    dims = ("NHWC", "HWIO", "NHWC")
    if s == 1 or s * s * cin > MXU_DEPTH or groups != 1:
        return jax.lax.conv_general_dilated(
            x, w, window_strides=(s, s), padding=[(p, p), (p, p)],
            dimension_numbers=dims, feature_group_count=groups)
    _, H, W, _ = x.shape
    oh, ow = (H + 2 * p - kh) // s + 1, (W + 2 * p - kw) // s + 1
    lead = -(-p // s)                      # leading padding, in s2d pixels
    t = lead * s - p
    w = jnp.pad(w, ((t, 0), (t, 0), (0, 0), (0, 0)))
    x = jnp.pad(x, ((0, 0), (0, -H % s), (0, -W % s), (0, 0)))
    xs, ws = space_to_depth(x, w, s)
    # the s2d rows and cols the output reads past the leading padding
    hs, wd = oh + ws.shape[0] - 1 - lead, ow + ws.shape[1] - 1 - lead
    xs = xs[:, :hs, :wd]
    return jax.lax.conv_general_dilated(
        xs, ws, window_strides=(1, 1),
        padding=[(lead, hs - xs.shape[1]), (lead, wd - xs.shape[2])],
        dimension_numbers=dims)


def depthwise_conv(w: jnp.ndarray, x: jnp.ndarray,
                   bias: jnp.ndarray | None = None
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The depthwise conv of x (NHWC) with w (k, k, 1, C), k odd, stride
    1, padding k // 2, plus ``bias``; and each output pixel's sum over
    channels in float32 (N, H, W), a LayerNorm's mean, which XLA would
    otherwise fuse into its own conv.

    A map larger than the kernel window runs as the ``depthwise_conv``
    Pallas kernel: a band of rows and its halo held in VMEM, float32 sums
    started from the bias; compiled where the program is lowered for a
    TPU, interpreted elsewhere.  A map no larger than the window
    (ConvNeXt-T's 7×7 stage 4) keeps XLA's conv, which a v5e runs faster
    there: 43% of such a map's window taps fall on padding."""
    k, c = w.shape[0], w.shape[-1]
    b = jnp.zeros((c,), x.dtype) if bias is None else bias
    if x.shape[1] > k and x.shape[2] > k:
        # imported here: Pallas takes seconds to import, which a model
        # without depthwise convs (ResNet18) should not pay at start-up
        from repro.kernels.depthwise_conv import depthwise_conv_kernel
        return jax.lax.platform_dependent(
            x, w, b,
            tpu=functools.partial(depthwise_conv_kernel, interpret=False),
            default=functools.partial(depthwise_conv_kernel, interpret=True))
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=[(k // 2, k // 2)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c) + b
    return y, jnp.sum(y.astype(jnp.float32), axis=-1)


def init_bn(cout: int, dtype) -> Params:
    return {"scale": jnp.ones((cout,), dtype), "bias": jnp.zeros((cout,), dtype),
            "mean": jnp.zeros((cout,), jnp.float32),
            "var": jnp.ones((cout,), jnp.float32)}


def batchnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """Inference-mode BN (folded running stats) — matches the PIM model's
    CONV_BN epilogue semantics."""
    inv = jax.lax.rsqrt(p["var"] + eps)
    return ((x.astype(jnp.float32) - p["mean"]) * inv).astype(x.dtype) \
        * p["scale"] + p["bias"]


def maxpool2d(x: jnp.ndarray, k: int, stride: int, padding: int) -> jnp.ndarray:
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, stride, stride, 1),
        [(0, 0), (padding, padding), (padding, padding), (0, 0)])


def avgpool_global(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(x, axis=(1, 2))
