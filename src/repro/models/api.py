"""Model assembly: config → (init, forward, init_cache, decode_step).

Layer stacks are STACKED pytrees scanned with ``lax.scan`` so the traced
HLO is O(one layer) regardless of depth — essential for the 512-device
dry-run compiles.  Heterogeneous architectures scan over repeating UNITS:

* zamba2 hybrid: 9 units × (5 mamba2 blocks + 1 shared-attn block)
* xlstm: 12 units × (3 mLSTM blocks + 1 sLSTM block)
* gemma2: homogeneous attn stack with a per-layer sliding-window array
* whisper: encoder stack + decoder stack (self + cross attention)

``forward`` is the training/prefill path; ``decode_step`` is the O(1)
serving path against a pre-allocated KV/state cache.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as B
from repro.models import layers as L

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[..., Params]
    forward: Callable[..., tuple[jnp.ndarray, jnp.ndarray]]
    init_cache: Callable[..., Params]
    decode_step: Callable[..., tuple[jnp.ndarray, Params]]


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)


def _stack_init(init_fn, key, n: int) -> Params:
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _sinusoid(seq: int, dim: int, dtype) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32)
                  * (-math.log(10000.0) / dim))
    pe = jnp.zeros((seq, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe.astype(dtype)


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# shared embed / head
# ---------------------------------------------------------------------------

def _init_embed(key, cfg, pdt) -> Params:
    p = {"embed": L.embed_init(key, cfg.vocab_size, cfg.d_model, pdt),
         "final_norm": L.init_rmsnorm(cfg.d_model, pdt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(jax.random.fold_in(key, 1), cfg.d_model,
                                    cfg.vocab_size, pdt)
    return p


def _embed(params, cfg, tokens):
    x = params["embed"][tokens]
    if cfg.scale_embed_by_sqrt_dim:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def _head(params, cfg, x):
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    logits = logits.astype(jnp.float32)
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits


# ---------------------------------------------------------------------------
# decoder-only transformer family (dense / moe / vlm)
# ---------------------------------------------------------------------------

def _build_decoder_only(cfg: ModelConfig) -> Model:
    dt, pdt = _dt(cfg)
    n_dense = cfg.first_dense_layers if cfg.moe_num_experts else 0
    n_stack = cfg.num_layers - n_dense
    windows = jnp.array([cfg.window_for_layer(i)
                         for i in range(n_dense, cfg.num_layers)], jnp.int32)
    use_moe = cfg.moe_num_experts > 0

    def init(key) -> Params:
        ks = jax.random.split(key, 3)
        p = _init_embed(ks[0], cfg, pdt)
        if n_dense:
            p["dense0"] = B.init_attn_block(ks[2], cfg, pdt, use_moe=False)
        p["layers"] = _stack_init(
            lambda k: B.init_attn_block(k, cfg, pdt, use_moe=use_moe),
            ks[1], n_stack)
        return p

    def forward(params, batch, *, remat: bool = False,
                return_hidden: bool = False):
        tokens = batch["tokens"]
        x = _embed(params, cfg, tokens).astype(dt)
        n_prefix = 0
        if cfg.num_prefix_tokens and "prefix_embed" in batch:
            pfx = batch["prefix_embed"].astype(dt)
            n_prefix = pfx.shape[1]
            x = jnp.concatenate([pfx, x], axis=1)
        Btch, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), (Btch, S))

        def body(carry, layer):
            h, aux = carry
            lp, win = layer
            mask = L.causal_mask(S, S, 0, 0) & _win_mask(S, win)
            h, a = B.attn_block(lp, h, cfg, positions=positions, mask=mask)
            return (h, aux + a), None

        body_fn = jax.checkpoint(body) if remat else body
        aux0 = jnp.float32(0.0)
        if n_dense:
            mask = L.causal_mask(S, S, 0, 0)
            x, a0 = B.attn_block(params["dense0"], x, cfg,
                                 positions=positions, mask=mask)
            aux0 = aux0 + a0
        (x, aux), _ = jax.lax.scan(body_fn, (x, aux0),
                                   (params["layers"], windows))
        if n_prefix:
            x = x[:, n_prefix:]
        if return_hidden:
            return x, aux
        return _head(params, cfg, x), aux

    def init_cache(batch_size: int, max_len: int) -> Params:
        total = max_len + cfg.num_prefix_tokens
        c = {"layers": jax.vmap(
            lambda _: B.init_attn_cache(cfg, batch_size, total, dt))(
                jnp.arange(n_stack))}
        if n_dense:
            c["dense0"] = B.init_attn_cache(cfg, batch_size, total, dt)
        return c

    def decode_step(params, cache, tokens, index):
        x = _embed(params, cfg, tokens).astype(dt)
        new_cache = dict(cache)
        if n_dense:
            x, c0, _ = B.attn_block_decode(params["dense0"], cache["dense0"],
                                           x, cfg, index=index)
            new_cache["dense0"] = c0

        def body(h, layer):
            lp, win, kc, vc = layer
            h, c, _ = B.attn_block_decode(lp, {"k": kc, "v": vc}, h, cfg,
                                          index=index, window=win)
            return h, (c["k"], c["v"])

        x, (ks_, vs_) = jax.lax.scan(
            body, x, (params["layers"], windows,
                      cache["layers"]["k"], cache["layers"]["v"]))
        new_cache["layers"] = {"k": ks_, "v": vs_}
        return _head(params, cfg, x), new_cache

    return Model(cfg, init, forward, init_cache, decode_step)


def _win_mask(S: int, window) -> jnp.ndarray:
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    w = jnp.asarray(window)
    return jnp.where(w > 0, kpos > qpos - w, True)[None]


# ---------------------------------------------------------------------------
# hybrid (zamba2): units of (E-1) mamba + 1 attn
# ---------------------------------------------------------------------------

def _build_hybrid(cfg: ModelConfig) -> Model:
    dt, pdt = _dt(cfg)
    E = cfg.hybrid_attn_every
    assert cfg.num_layers % E == 0, "hybrid layers must tile into units"
    U, K = cfg.num_layers // E, E - 1

    def init(key) -> Params:
        ks = jax.random.split(key, 3)
        p = _init_embed(ks[0], cfg, pdt)
        p["mamba"] = _stack_init(
            lambda k: jax.vmap(
                lambda kk: B.init_mamba_block(kk, cfg, pdt))(
                    jax.random.split(k, K)), ks[1], U)
        p["attn"] = _stack_init(
            lambda k: B.init_attn_block(k, cfg, pdt, use_moe=False), ks[2], U)
        return p

    def forward(params, batch, *, remat: bool = False,
                return_hidden: bool = False):
        tokens = batch["tokens"]
        x = _embed(params, cfg, tokens).astype(dt)
        Btch, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S), (Btch, S))
        mask = L.causal_mask(S, S)

        def unit(h, up):
            mp, ap = up

            def inner(hh, lp):
                return B.mamba_block(lp, hh, cfg), None

            h, _ = jax.lax.scan(inner, h, mp)
            h, _ = B.attn_block(ap, h, cfg, positions=positions, mask=mask)
            return h, None

        unit_fn = jax.checkpoint(unit) if remat else unit
        x, _ = jax.lax.scan(unit_fn, x, (params["mamba"], params["attn"]))
        if return_hidden:
            return x, jnp.float32(0.0)
        return _head(params, cfg, x), jnp.float32(0.0)

    def init_cache(batch_size: int, max_len: int) -> Params:
        mcache = jax.vmap(lambda _: jax.vmap(
            lambda __: SSMCACHE(cfg, batch_size, dt))(jnp.arange(K)))(
                jnp.arange(U))
        acache = jax.vmap(lambda _: B.init_attn_cache(
            cfg, batch_size, max_len, dt))(jnp.arange(U))
        return {"mamba": mcache, "attn": acache}

    def decode_step(params, cache, tokens, index):
        x = _embed(params, cfg, tokens).astype(dt)

        def unit(h, up):
            mp, ap, mc, kc, vc = up

            def inner(hh, inner_in):
                lp, c = inner_in
                hh, cnew = B.mamba_block_decode(lp, c, hh, cfg)
                return hh, cnew

            h, mc_new = jax.lax.scan(inner, h, (mp, mc))
            h, ac, _ = B.attn_block_decode(ap, {"k": kc, "v": vc}, h, cfg,
                                           index=index)
            return h, (mc_new, ac["k"], ac["v"])

        x, (mc, ks_, vs_) = jax.lax.scan(
            unit, x, (params["mamba"], params["attn"], cache["mamba"],
                      cache["attn"]["k"], cache["attn"]["v"]))
        return _head(params, cfg, x), {"mamba": mc,
                                       "attn": {"k": ks_, "v": vs_}}

    return Model(cfg, init, forward, init_cache, decode_step)


def SSMCACHE(cfg, batch, dt):
    from repro.models.ssm import mamba2_init_cache
    return mamba2_init_cache(cfg, batch, dt)


# ---------------------------------------------------------------------------
# xLSTM: units of (E-1) mLSTM + 1 sLSTM
# ---------------------------------------------------------------------------

def _build_xlstm(cfg: ModelConfig) -> Model:
    dt, pdt = _dt(cfg)
    E = cfg.xlstm_slstm_every
    assert E and cfg.num_layers % E == 0
    U, K = cfg.num_layers // E, E - 1

    def init(key) -> Params:
        ks = jax.random.split(key, 3)
        p = _init_embed(ks[0], cfg, pdt)
        p["mlstm"] = _stack_init(
            lambda k: jax.vmap(
                lambda kk: B.init_mlstm_block(kk, cfg, pdt))(
                    jax.random.split(k, K)), ks[1], U)
        p["slstm"] = _stack_init(
            lambda k: B.init_slstm_block(k, cfg, pdt), ks[2], U)
        return p

    def forward(params, batch, *, remat: bool = False,
                return_hidden: bool = False):
        x = _embed(params, cfg, batch["tokens"]).astype(dt)

        def unit(h, up):
            mp, sp = up

            def inner(hh, lp):
                return B.mlstm_block(lp, hh, cfg), None

            h, _ = jax.lax.scan(inner, h, mp)
            h = B.slstm_block(sp, h, cfg)
            return h, None

        unit_fn = jax.checkpoint(unit) if remat else unit
        x, _ = jax.lax.scan(unit_fn, x, (params["mlstm"], params["slstm"]))
        if return_hidden:
            return x, jnp.float32(0.0)
        return _head(params, cfg, x), jnp.float32(0.0)

    def init_cache(batch_size: int, max_len: int) -> Params:
        from repro.models.xlstm import mlstm_init_cache, slstm_init_cache
        mc = jax.vmap(lambda _: jax.vmap(
            lambda __: mlstm_init_cache(cfg, batch_size))(jnp.arange(K)))(
                jnp.arange(U))
        sc = jax.vmap(lambda _: slstm_init_cache(cfg, batch_size))(
            jnp.arange(U))
        return {"mlstm": mc, "slstm": sc}

    def decode_step(params, cache, tokens, index):
        x = _embed(params, cfg, batch_tokens := tokens).astype(dt)

        def unit(h, up):
            mp, sp, mc, sc = up

            def inner(hh, inner_in):
                lp, c = inner_in
                hh, cnew = B.mlstm_block_decode(lp, c, hh, cfg)
                return hh, cnew

            h, mc_new = jax.lax.scan(inner, h, (mp, mc))
            h, sc_new = B.slstm_block_decode(sp, sc, h, cfg)
            return h, (mc_new, sc_new)

        x, (mc, sc) = jax.lax.scan(
            unit, x, (params["mlstm"], params["slstm"], cache["mlstm"],
                      cache["slstm"]))
        return _head(params, cfg, x), {"mlstm": mc, "slstm": sc}

    return Model(cfg, init, forward, init_cache, decode_step)


# ---------------------------------------------------------------------------
# encoder-decoder (whisper backbone; conv frontend stubbed)
# ---------------------------------------------------------------------------

def _build_encdec(cfg: ModelConfig) -> Model:
    dt, pdt = _dt(cfg)

    def init(key) -> Params:
        ks = jax.random.split(key, 3)
        p = _init_embed(ks[0], cfg, pdt)
        p["enc"] = _stack_init(
            lambda k: B.init_attn_block(k, cfg, pdt, use_moe=False),
            ks[1], cfg.encoder_layers)
        p["enc_norm"] = L.init_rmsnorm(cfg.d_model, pdt)
        p["dec"] = _stack_init(
            lambda k: B.init_attn_block(k, cfg, pdt, use_moe=False,
                                        cross=True), ks[2], cfg.num_layers)
        return p

    def encode(params, frames):
        Btch, F, _ = frames.shape
        x = frames.astype(dt) + _sinusoid(F, cfg.d_model, dt)[None]
        positions = jnp.broadcast_to(jnp.arange(F), (Btch, F))
        mask = jnp.ones((1, F, F), bool)

        def body(h, lp):
            h, _ = B.attn_block(lp, h, cfg, positions=positions, mask=mask)
            return h, None

        x, _ = jax.lax.scan(body, x, params["enc"])
        return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)

    def forward(params, batch, *, remat: bool = False,
                return_hidden: bool = False):
        tokens = batch["tokens"]
        enc_out = encode(params, batch["enc_frames"])
        Btch, S = tokens.shape
        x = _embed(params, cfg, tokens).astype(dt)
        x = x + _sinusoid(S, cfg.d_model, dt)[None]
        positions = jnp.broadcast_to(jnp.arange(S), (Btch, S))
        mask = L.causal_mask(S, S)
        enc_mask = jnp.ones((1, S, enc_out.shape[1]), bool)

        def body(h, lp):
            h, a = B.attn_block(lp, h, cfg, positions=positions, mask=mask,
                                enc_out=enc_out, enc_mask=enc_mask)
            return h, None

        body_fn = jax.checkpoint(body) if remat else body
        x, _ = jax.lax.scan(body_fn, x, params["dec"])
        if return_hidden:
            return x, jnp.float32(0.0)
        return _head(params, cfg, x), jnp.float32(0.0)

    def init_cache(batch_size: int, max_len: int) -> Params:
        return {"dec": jax.vmap(lambda _: B.init_attn_cache(
            cfg, batch_size, max_len, dt, cross_len=cfg.encoder_seq_len))(
                jnp.arange(cfg.num_layers))}

    def fill_cross_cache(params, cache, frames) -> Params:
        """Prefill the cross-attention k/v from encoder output."""
        enc_out = encode(params, frames)
        Btch, F, _ = enc_out.shape
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim

        def per_layer(lp):
            k = (enc_out @ lp["xattn"]["wk"]).reshape(Btch, F, kv, hd)
            v = (enc_out @ lp["xattn"]["wv"]).reshape(Btch, F, kv, hd)
            return k.astype(dt), v.astype(dt)

        ks_, vs_ = jax.vmap(per_layer)(params["dec"])
        dec = dict(cache["dec"])
        dec.update(xk=ks_, xv=vs_)
        return {"dec": dec}

    def decode_step(params, cache, tokens, index):
        x = _embed(params, cfg, tokens).astype(dt)
        pos_emb = jax.lax.dynamic_slice_in_dim(
            _sinusoid(cache["dec"]["k"].shape[2], cfg.d_model, dt), index, 1)
        x = x + pos_emb[None]

        def body(h, layer):
            lp, kc, vc, xkc, xvc = layer
            h, c, _ = B.attn_block_decode(
                lp, {"k": kc, "v": vc, "xk": xkc, "xv": xvc}, h, cfg,
                index=index)
            return h, (c["k"], c["v"])

        x, (ks_, vs_) = jax.lax.scan(
            body, x, (params["dec"], cache["dec"]["k"], cache["dec"]["v"],
                      cache["dec"]["xk"], cache["dec"]["xv"]))
        dec = dict(cache["dec"])
        dec.update(k=ks_, v=vs_)
        return _head(params, cfg, x), {"dec": dec}

    m = Model(cfg, init, forward, init_cache, decode_step)
    object.__setattr__(m, "fill_cross_cache", fill_cross_cache)
    object.__setattr__(m, "encode", encode)
    return m


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

# image classifiers by config name: (module of repro.models, init function)
CNNS = {"resnet18": ("resnet", "init_resnet18"),
        "convnext-tiny": ("convnext", "init_convnext_tiny")}


def _build_cnn(cfg: ModelConfig) -> Model:
    """An image classifier: ``vocab_size`` classes, no decode path."""
    module, init_name = CNNS[cfg.name]
    net = importlib.import_module(f"repro.models.{module}")
    init_net = getattr(net, init_name)
    dtype = jnp.dtype(cfg.param_dtype)

    def init(key):
        return init_net(key, cfg.vocab_size, dtype)

    def fwd(params, batch, *, remat: bool = False,
            return_hidden: bool = False):
        return net.forward(params, batch["images"]), jnp.float32(0.0)

    def no_cache(*a, **k):
        raise NotImplementedError("CNN classifier has no decode path")

    return Model(cfg, init, fwd, no_cache, no_cache)


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "cnn":
        return _build_cnn(cfg)
    if cfg.is_encoder_decoder:
        return _build_encdec(cfg)
    if cfg.family == "hybrid":
        return _build_hybrid(cfg)
    if cfg.family == "ssm" and cfg.xlstm_slstm_every:
        return _build_xlstm(cfg)
    return _build_decoder_only(cfg)
