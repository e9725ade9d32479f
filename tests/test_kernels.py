"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all against the pure-jnp oracles in ``repro.kernels.ref``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import ops  # noqa: E402
from repro.kernels.flash_attention import flash_attention_kernel  # noqa: E402
from repro.kernels.fused_conv import fused_conv_kernel  # noqa: E402
from repro.kernels.mamba_scan import mamba_scan_kernel  # noqa: E402
from repro.kernels.mlstm_scan import mlstm_scan_kernel  # noqa: E402
from repro.kernels.ref import (attention_ref, fused_conv_ref, mamba_scan_ref,  # noqa: E402
                               mlstm_ref)

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("BH,BKV,S,T,D", [
    (4, 2, 128, 128, 64),
    (2, 1, 64, 128, 32),
    (8, 8, 128, 128, 128),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(BH, BKV, S, T, D, causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (BH, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (BKV, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (BKV, T, D), jnp.float32)
    out = flash_attention_kernel(q, k, v, causal=causal, block_q=64,
                                 block_k=64)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (64, 0.0), (0, 30.0),
                                            (32, 50.0)])
def test_flash_attention_window_softcap(window, softcap):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 32))
    k = jax.random.normal(ks[1], (2, 128, 32))
    v = jax.random.normal(ks[2], (2, 128, 32))
    out = flash_attention_kernel(q, k, v, causal=True, window=window,
                                 softcap=softcap, block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=True, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 64)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 128, 64)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 128, 64)).astype(jnp.bfloat16)
    out = flash_attention_kernel(q, k, v, block_q=64, block_k=64)
    ref = attention_ref(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


def test_flash_attention_ops_wrapper_gqa():
    ks = jax.random.split(KEY, 3)
    B, S, H, KV, D = 2, 128, 8, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    # oracle via per-batch flattened layout
    ref = attention_ref(q.transpose(0, 2, 1, 3).reshape(B * H, S, D),
                        k.transpose(0, 2, 1, 3).reshape(B * KV, S, D),
                        v.transpose(0, 2, 1, 3).reshape(B * KV, S, D))
    ref = ref.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(1, 3), st.integers(16, 64))
def test_flash_attention_property_rowsum(bh_mult, kv, dim):
    """Softmax row-stochasticity: output of attention over CONSTANT values
    equals that constant (any mask/shape)."""
    BH = kv * bh_mult
    S = 64
    D = (dim // 8) * 8 or 8
    ks = jax.random.split(jax.random.PRNGKey(bh_mult * 100 + kv), 2)
    q = jax.random.normal(ks[0], (BH, S, D))
    k = jax.random.normal(ks[1], (kv, S, D))
    v = jnp.ones((kv, S, D))
    out = flash_attention_kernel(q, k, v, causal=True, block_q=32,
                                 block_k=32)
    np.testing.assert_allclose(np.asarray(out), 1.0, atol=1e-5)


# ---------------------------------------------------------------------------
# fused conv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,s,p", [(3, 1, 1), (3, 2, 1), (1, 1, 0),
                                   (1, 2, 0), (7, 2, 3)])
@pytest.mark.parametrize("relu", [True, False])
def test_fused_conv_geometry(k, s, p, relu):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (2, 16, 16, 8))
    w = jax.random.normal(ks[1], (k, k, 8, 16)) * 0.2
    scale = jax.random.normal(ks[2], (16,)) * 0.1 + 1.0
    shift = jax.random.normal(ks[3], (16,)) * 0.1
    out = fused_conv_kernel(x, w, scale, shift, stride=s, padding=p,
                            relu=relu, tile_h=4, cout_block=8,
                            interpret=True)
    ref = fused_conv_ref(x, w, scale, shift, stride=s, padding=p, relu=relu)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_fused_conv_residual_add_relu():
    """The paper's full fused epilogue: CONV_BN + ADD + RELU in one kernel."""
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (1, 8, 8, 8))
    w = jax.random.normal(ks[1], (3, 3, 8, 8)) * 0.2
    scale = jnp.ones((8,))
    shift = jnp.zeros((8,))
    res = jax.random.normal(ks[2], (1, 8, 8, 8))
    out = fused_conv_kernel(x, w, scale, shift, residual=res, tile_h=4,
                            cout_block=8, interpret=True)
    ref = fused_conv_ref(x, w, scale, shift, residual=res)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    assert (np.asarray(out) >= 0).all()  # relu applied after add


def test_fused_conv_nondivisible_spatial():
    """Odd extents exercise the pad+crop path (ResNet 7x7 stage-4 maps)."""
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (1, 7, 7, 8))
    w = jax.random.normal(ks[1], (3, 3, 8, 8)) * 0.2
    out = fused_conv_kernel(x, w, jnp.ones((8,)), jnp.zeros((8,)),
                            tile_h=4, cout_block=8, interpret=True)
    ref = fused_conv_ref(x, w, jnp.ones((8,)), jnp.zeros((8,)))
    assert out.shape == ref.shape == (1, 7, 7, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(st.integers(4, 12), st.integers(1, 2), st.sampled_from([1, 3]))
def test_fused_conv_property(hw, stride, k):
    p = k // 2
    key = jax.random.PRNGKey(hw * 10 + stride)
    ks = jax.random.split(key, 2)
    x = jax.random.normal(ks[0], (1, hw, hw, 4))
    w = jax.random.normal(ks[1], (k, k, 4, 8)) * 0.3
    out = fused_conv_kernel(x, w, jnp.ones((8,)), jnp.zeros((8,)),
                            stride=stride, padding=p, tile_h=2,
                            cout_block=8, interpret=True)
    ref = fused_conv_ref(x, w, jnp.ones((8,)), jnp.zeros((8,)),
                         stride=stride, padding=p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# mamba scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,chunk", [(64, 16), (64, 64), (128, 32)])
def test_mamba_scan(S, chunk):
    b, H, P, N = 2, 3, 16, 8
    ks = jax.random.split(KEY, 4)
    dtx = jax.random.normal(ks[0], (b, S, H, P)) * 0.3
    a_log = -jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    Bm = jax.random.normal(ks[2], (b, S, N)) * 0.3
    Cm = jax.random.normal(ks[3], (b, S, N)) * 0.3
    y = mamba_scan_kernel(dtx, a_log, Bm, Cm, chunk=chunk)
    ref = mamba_scan_ref(dtx, a_log, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)


def test_mamba_scan_chunk_invariance():
    """Chunk size must not change the result (state carry correctness)."""
    b, S, H, P, N = 1, 64, 2, 8, 4
    ks = jax.random.split(KEY, 4)
    dtx = jax.random.normal(ks[0], (b, S, H, P)) * 0.3
    a_log = -jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    Bm = jax.random.normal(ks[2], (b, S, N)) * 0.3
    Cm = jax.random.normal(ks[3], (b, S, N)) * 0.3
    y16 = mamba_scan_kernel(dtx, a_log, Bm, Cm, chunk=16)
    y64 = mamba_scan_kernel(dtx, a_log, Bm, Cm, chunk=64)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y64), atol=1e-4)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 1000))
def test_mamba_scan_decay_property(seed):
    """With a_log = -inf-ish (full reset each step), y_t depends only on
    step t inputs: y_t = (C_t·B_t)·dtx_t."""
    b, S, H, P, N = 1, 32, 2, 8, 4
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    dtx = jax.random.normal(ks[0], (b, S, H, P)) * 0.3
    a_log = jnp.full((b, S, H), -30.0)
    Bm = jax.random.normal(ks[1], (b, S, N)) * 0.3
    Cm = jax.random.normal(ks[2], (b, S, N)) * 0.3
    y = mamba_scan_kernel(dtx, a_log, Bm, Cm, chunk=16)
    expect = jnp.einsum("bsn,bsn->bs", Cm, Bm)[..., None, None] * dtx
    np.testing.assert_allclose(np.asarray(y), np.asarray(expect), atol=1e-4)


# ---------------------------------------------------------------------------
# mlstm scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,chunk", [(32, 8), (64, 64)])
def test_mlstm_scan(S, chunk):
    b, H, P = 2, 2, 16
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, S, H, P)) * 0.4
    k = jax.random.normal(ks[1], (b, S, H, P)) * 0.4
    v = jax.random.normal(ks[2], (b, S, H, P)) * 0.4
    ip = jax.random.normal(ks[3], (b, S, H))
    fp = jax.random.normal(ks[4], (b, S, H)) + 2
    h = mlstm_scan_kernel(q, k, v, ip, fp, chunk=chunk)
    ref = mlstm_ref(q, k, v, ip, fp)
    np.testing.assert_allclose(np.asarray(h), np.asarray(ref), atol=1e-4)


def test_mlstm_chunk_invariance():
    b, S, H, P = 1, 32, 1, 8
    ks = jax.random.split(KEY, 5)
    args = [jax.random.normal(k_, (b, S, H, P)) * 0.4 for k_ in ks[:3]]
    ip = jax.random.normal(ks[3], (b, S, H))
    fp = jax.random.normal(ks[4], (b, S, H)) + 2
    h8 = mlstm_scan_kernel(*args, ip, fp, chunk=8)
    h32 = mlstm_scan_kernel(*args, ip, fp, chunk=32)
    np.testing.assert_allclose(np.asarray(h8), np.asarray(h32), atol=1e-4)
