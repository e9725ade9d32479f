"""Unit/property tests for core layers: RoPE, norms, masks, attention,
and Swin's windows."""

import re

import jax
import jax.extend as jex
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.models import layers as L  # noqa: E402
from repro.models import swin  # noqa: E402

KEY = jax.random.PRNGKey(11)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def test_rope_preserves_norm():
    x = jax.random.normal(KEY, (2, 8, 4, 32))
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    y = L.apply_rope(x, pos, 10000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(y), axis=-1),
                               rtol=1e-5)


def test_rope_relative_property():
    """⟨rope(q,m), rope(k,n)⟩ depends only on m−n."""
    q = jax.random.normal(KEY, (1, 1, 1, 16))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 1, 1, 16))

    def dot_at(m, n):
        qm = L.apply_rope(q, jnp.array([[m]]), 10000.0)
        kn = L.apply_rope(k, jnp.array([[n]]), 10000.0)
        return float(jnp.sum(qm * kn))

    assert dot_at(3, 1) == pytest.approx(dot_at(7, 5), rel=1e-4)
    assert dot_at(0, 0) == pytest.approx(dot_at(9, 9), rel=1e-4)


def test_rope_zero_theta_is_identity():
    x = jax.random.normal(KEY, (1, 4, 2, 8))
    pos = jnp.broadcast_to(jnp.arange(4), (1, 4))
    np.testing.assert_array_equal(np.asarray(L.apply_rope(x, pos, 0.0)),
                                  np.asarray(x))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000))
def test_rmsnorm_unit_rms(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (4, 32)) * 5
    y = L.rmsnorm(jnp.ones((32,)), x)
    rms = np.sqrt(np.mean(np.square(np.asarray(y)), axis=-1))
    np.testing.assert_allclose(rms, 1.0, atol=1e-3)


def test_rmsnorm_scale_equivariance():
    """rmsnorm(c·x) == rmsnorm(x) for c > 0 (scale invariant)."""
    x = jax.random.normal(KEY, (2, 16))
    a = L.rmsnorm(jnp.ones((16,)), x)
    b = L.rmsnorm(jnp.ones((16,)), 7.0 * x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_layernorm_zero_mean_unit_var():
    p = L.init_layernorm(32, jnp.float32)
    x = jax.random.normal(KEY, (4, 32)) * 3 + 2
    y = np.asarray(L.layernorm(p, x))
    np.testing.assert_allclose(y.mean(-1), 0.0, atol=1e-4)
    np.testing.assert_allclose(y.std(-1), 1.0, atol=1e-2)


# ---------------------------------------------------------------------------
# masks / attention semantics
# ---------------------------------------------------------------------------

def test_causal_mask_offsets():
    m = np.asarray(L.causal_mask(2, 6, q_offset=4))
    # query global positions 4,5 attend to keys 0..4 / 0..5
    assert m[0, 0].tolist() == [True] * 5 + [False]
    assert m[0, 1].tolist() == [True] * 6


def test_causal_mask_window():
    m = np.asarray(L.causal_mask(4, 4, window=2))
    assert m[0, 3].tolist() == [False, False, True, True]


def test_softcap_bounds_logits():
    x = jnp.linspace(-500, 500, 11)
    y = np.asarray(L._softcap(x, 50.0))
    assert (np.abs(y) <= 50.0 + 1e-4).all()
    # approximately identity near zero
    assert L._softcap(jnp.asarray(1.0), 50.0) == pytest.approx(1.0, rel=1e-3)


def test_attention_scores_gqa_equivalence():
    """GQA with kv groups == MHA with repeated kv heads."""
    B, S, H, KV, D = 1, 8, 4, 2, 16
    q = jax.random.normal(KEY, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, KV, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, KV, D))
    mask = L.causal_mask(S, S)
    out_gqa = L.attention_scores(q, k, v, mask)
    out_mha = L.attention_scores(q, jnp.repeat(k, 2, axis=2),
                                 jnp.repeat(v, 2, axis=2), mask)
    # repeated-kv MHA maps head h to kv h//2 in GQA ordering
    np.testing.assert_allclose(np.asarray(out_gqa), np.asarray(out_mha),
                               atol=1e-5)


@pytest.mark.parametrize("bias_shape", [(2, 3, 6, 6), (1, 3, 6, 6)])
def test_attention_scores_adds_bias_before_the_softmax(bias_shape):
    B, S, H, D = 2, 6, 3, 8
    q, k, v = (jax.random.normal(jax.random.fold_in(KEY, i), (B, S, H, D))
               for i in range(3))
    bias = 2.0 * jax.random.normal(jax.random.fold_in(KEY, 3), bias_shape)
    with jax.default_matmul_precision("highest"):
        out = L.attention_scores(q, k, v, None, bias=bias)
        logits = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(D) + bias
        want = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def _attention_scores_before_bias(q, k, v, mask, softcap=0.0):
    """``attention_scores`` as it was before it took ``bias``."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(hd)
    logits = L._softcap(logits, softcap)
    m = mask.reshape(B, KV, G, S, T) if mask.ndim == 4 and mask.shape[1] == H \
        else mask[:, None, None, :, :] if mask.ndim == 3 else mask
    logits = jnp.where(m, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


@pytest.mark.parametrize("per_head_mask", [False, True])
@pytest.mark.parametrize("softcap", [0.0, 50.0])
def test_attention_scores_without_bias_compiles_as_before(per_head_mask,
                                                          softcap):
    """With ``bias=None`` (every LM caller) the compiled program is the
    one from before the argument, metadata aside."""
    B, S, H, KV, D = 2, 8, 4, 2, 16
    q = jnp.zeros((B, S, H, D))
    k = v = jnp.zeros((B, S, KV, D))
    mask = L.causal_mask(S, S)
    if per_head_mask:
        mask = jnp.broadcast_to(mask[:, None], (B, H, S, S))

    def hlo(fn):
        text = jax.jit(lambda q, k, v, m: fn(q, k, v, m, softcap)).lower(
            q, k, v, mask).compile().as_text()
        # the source-location tables and each op's metadata name the code
        blocks = [b for b in text.split("\n\n") if b.split("\n")[0] not in
                  ("FileNames", "FunctionNames", "FileLocations", "StackFrames")]
        return re.sub(r", metadata=\{[^}]*\}", "", "\n\n".join(blocks))
    assert hlo(L.attention_scores) == hlo(_attention_scores_before_bias)


# ---------------------------------------------------------------------------
# Swin's windows (repro.models.swin)
# ---------------------------------------------------------------------------

def test_window_partition_then_reverse_is_the_identity():
    x = jax.random.normal(KEY, (2, 14, 21, 5))
    xw = swin.window_partition(x, 7)
    assert xw.shape == (2 * 2 * 3, 49, 5)
    # the second window of the first image: rows 0-6, columns 7-13
    np.testing.assert_array_equal(np.asarray(xw[1]).reshape(7, 7, 5),
                                  np.asarray(x[0, :7, 7:14]))
    np.testing.assert_array_equal(np.asarray(swin.window_reverse(xw, 7, 14, 21)),
                                  np.asarray(x))


def test_shift_mask_is_the_published_slice_labelled_mask():
    """The official construction: label the map's 3x3 slice regions, cut
    the labels into windows, and put -100 wherever two labels differ."""
    hw, w, s = 14, 7, 3
    img = np.zeros((1, hw, hw, 1))
    cnt = 0
    for hs in (slice(0, -w), slice(-w, -s), slice(-s, None)):
        for ws in (slice(0, -w), slice(-w, -s), slice(-s, None)):
            img[:, hs, ws, :] = cnt
            cnt += 1
    windows = img.reshape(1, hw // w, w, hw // w, w, 1).transpose(
        0, 1, 3, 2, 4, 5).reshape(-1, w * w)
    diff = windows[:, None, :] - windows[:, :, None]
    want = np.where(diff != 0, -100.0, 0.0)
    np.testing.assert_array_equal(swin.shift_mask(hw, w, s), want)
    # the top-left window holds one region; the bottom-right one four
    assert not want[0].any() and len(np.unique(windows[-1])) == 4


def test_relative_index_is_the_offset_in_a_13_by_13_table():
    w = 7
    want = np.zeros((w * w, w * w), int)
    for i in range(w * w):
        for j in range(w * w):
            (yi, xi), (yj, xj) = divmod(i, w), divmod(j, w)
            want[i, j] = (yi - yj + 6) * 13 + (xi - xj + 6)
    np.testing.assert_array_equal(swin.relative_index(w), want)


# ---------------------------------------------------------------------------
# conv/pool (resnet substrate)
# ---------------------------------------------------------------------------

def test_conv2d_identity_kernel():
    x = jax.random.normal(KEY, (1, 5, 5, 3))
    w = jnp.zeros((1, 1, 3, 3)).at[0, 0].set(jnp.eye(3))
    np.testing.assert_allclose(np.asarray(L.conv2d(w, x)), np.asarray(x),
                               atol=1e-6)


@pytest.mark.parametrize("hw, k, stride, pad, cin, cout, features, step", [
    (224, 7, 2, 3, 3, 64, 12, 1),     # the stem: space-to-depth, 2·2·3
    (64, 7, 2, 3, 3, 64, 12, 1),
    (65, 7, 2, 3, 3, 64, 12, 1),      # odd: a row and a col padded
    (56, 3, 2, 1, 64, 128, 64, 2),    # 2·2·64 > 128: the plain strided conv
    (56, 1, 2, 0, 64, 128, 64, 2),
    (224, 4, 4, 0, 3, 96, 48, 1),     # ConvNeXt's patchify stem: 4·4·3
    (56, 2, 2, 0, 96, 192, 96, 2),    # its downsample: 2·2·96 > 128
], ids=["stem-224", "stem-64", "stem-65", "3x3s2-c64", "1x1s2-c64",
        "patchify-224", "down-2x2s2-c96"])
def test_conv2d_strided_matches_lax_and_takes_space_to_depth_by_shape(
        hw, k, stride, pad, cin, cout, features, step):
    x = jax.random.normal(KEY, (2, hw, hw, cin))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (k, k, cin, cout))
    ref = jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    with jax.default_matmul_precision("highest"):
        y = L.conv2d(w, x, stride, pad)
        jaxpr = jax.make_jaxpr(L.conv2d, static_argnums=(2, 3))(
            w, x, stride, pad)
    assert y.shape == ref.shape
    err = jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref))
    assert float(err) <= 1e-6
    convs = [e for e in jaxpr.eqns if e.primitive.name == "conv_general_dilated"]
    assert len(convs) == 1
    assert convs[0].invars[0].aval.shape[-1] == features
    assert convs[0].params["window_strides"] == (step, step)


@pytest.mark.parametrize("n, hw, k, c", [
    (2, 56, 7, 96), (2, 28, 7, 192), (2, 14, 7, 384),   # ConvNeXt-T stages
    (2, 7, 7, 768),     # stage 4: no larger than the window, XLA's conv
    (2, 9, 3, 5),
    (128, 9, 7, 16),    # the batch fills the lanes: the kernel sums channels
], ids=["stage1", "stage2", "stage3", "stage4", "9x9-k3-c5", "b128-c16"])
def test_conv2d_depthwise_matches_shifted_sum(n, hw, k, c):
    """``groups = C``: each channel convolved with its own k×k kernel, a
    sum of k·k shifted elementwise products; ``depthwise_conv`` adds the
    bias and gives its output's sum over channels."""
    x = jax.random.normal(KEY, (n, hw, hw, c))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (k, k, 1, c))
    b = jax.random.normal(jax.random.fold_in(KEY, 2), (c,))
    p = k // 2
    xp = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
    ref = sum(xp[:, i:i + hw, j:j + hw] * w[i, j, 0]
              for i in range(k) for j in range(k))
    with jax.default_matmul_precision("highest"):
        y = L.conv2d(w, x, 1, p, groups=c)
        yb, total = L.depthwise_conv(w, x, b)

    def err(a, r):
        return float(jnp.max(jnp.abs(a - r)) / jnp.max(jnp.abs(r)))
    assert y.shape == yb.shape == ref.shape
    assert err(y, ref) <= 1e-6
    assert err(yb, ref + b) <= 1e-6
    assert err(total, (ref + b).sum(-1)) <= 1e-6


@pytest.mark.parametrize("n", [128, 1])
def test_depthwise_tile_plan_covers_rows_once(n):
    """At ConvNeXt-T's stage shapes the bands cover every output row once,
    their windows at most the k − 1 halo rows more, and the input is
    read from HBM at most twice."""
    from repro.kernels.depthwise_conv import tile_plan
    for hw, c in [(56, 96), (28, 192), (14, 384), (7, 768)]:
        plan = tile_plan(n, hw, hw, c, 7)
        bands = [range(t, t + plan.rows) for t in range(0, hw, plan.rows)]
        assert sorted(r for band in bands for r in band) == list(range(hw))
        read = sum(len(range(max(band.start - 3, 0), min(band.stop + 3, hw)))
                   for band in bands)
        assert plan.read_factor == read / hw <= 2
        assert plan.batch_in_lanes == (n == 128 and c < 384)


def calls_kernel(eqn) -> bool:
    """Whether the equation, or one inside it, is a Pallas call."""
    if eqn.primitive.name == "pallas_call":
        return True
    subs = [v for p in eqn.params.values()
            for v in (p if isinstance(p, (tuple, list)) else (p,))
            if isinstance(v, (jex.core.Jaxpr, jex.core.ClosedJaxpr))]
    return any(calls_kernel(e) for sub in subs
               for e in getattr(sub, "jaxpr", sub).eqns)


def test_resnet18_convs_lower_ungrouped_and_convnext_depthwise():
    """ResNet18's twenty convs are ungrouped; ConvNeXt-T's eighteen
    depthwise convs are fifteen calls of the depthwise kernel (stages 1-3)
    and three grouped convs (stage 4's 7x7 maps), its other convs (stem,
    downsamples) ungrouped."""
    from repro.models import convnext, resnet
    x = jnp.zeros((1, 224, 224, 3))

    def lowered(init, forward):
        p = jax.eval_shape(init, jax.random.key(0))
        eqns = jax.make_jaxpr(forward)(p, x).eqns
        groups = [e.params["feature_group_count"] for e in eqns
                  if e.primitive.name == "conv_general_dilated"]
        return groups, [e for e in eqns if calls_kernel(e)]

    counts, kernels = lowered(resnet.init_resnet18, resnet.forward)
    assert len(counts) == 20 and set(counts) == {1} and not kernels
    counts, kernels = lowered(convnext.init_convnext_tiny, convnext.forward)
    depthwise = [c for c in counts if c != 1]
    assert len(counts) - len(depthwise) == 4
    assert depthwise == [convnext.DIMS[-1]] * convnext.DEPTHS[-1]
    assert len(kernels) == sum(convnext.DEPTHS[:-1])


def test_gelu_is_exact():
    x = jnp.linspace(-6.0, 6.0, 1001)
    np.testing.assert_allclose(np.asarray(L.gelu(x)),
                               np.asarray(jax.nn.gelu(x, approximate=False)),
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(L.gelu(x) - jax.nn.gelu(x)))) > 1e-4


def _ordinal(v: np.ndarray) -> np.ndarray:
    """float32 values as integers in the order of the floats: neighbours
    differ by one, and the gap between two values counts ulps."""
    i = v.view(np.int32).astype(np.int64)
    return np.where(i < 0, -(i & 0x7FFFFFFF), i)


def test_convnext_mlp_erf_within_ulps_of_lax_erf():
    """The block-MLP kernel writes erf as a float32 rational (Mosaic lowers
    no erf).  Over 2^21 steps of [-6, 6], both tails from 1e-38 to 1e38,
    ±0 and ±inf it is at most 8 ulps from ``jax.lax.erf``, keeps the sign
    of zero, and on normal floats it is at most 6 ulps from erf in float64
    (``jax.lax.erf``: 5); the largest gaps lie near |x| = 3.7, where erf
    is within 1e-7 of 1."""
    from scipy.special import erf as erf64

    from repro.kernels.convnext_mlp import erf
    tails = np.logspace(-38, 38, 20001).astype(np.float32)
    x = np.concatenate([np.linspace(-6, 6, 2**21 + 1, dtype=np.float32),
                        tails, -tails,
                        np.float32([0.0, -0.0, np.inf, -np.inf])])
    y = np.asarray(jax.jit(erf)(x))
    lax = np.asarray(jax.lax.erf(x))
    assert np.abs(_ordinal(y) - _ordinal(lax)).max() <= 8
    assert list(np.signbit(y[-4:-2])) == [False, True]
    normal = (np.abs(x) >= np.finfo(np.float32).tiny) | (x == 0)
    exact = erf64(x[normal].astype(np.float64)).astype(np.float32)
    assert np.abs(_ordinal(y[normal]) - _ordinal(exact)).max() <= 6


@pytest.mark.parametrize("n, hw, c", [
    (8, 5, 96),         # stage-1 width: 25 pixels, the last tile past the map
    (8, 4, 192),        # stage-2 width
    (128, 3, 96),       # a whole lane tile of batch, nothing padded
], ids=["stage1-b8", "stage2-b8", "stage1-b128"])
def test_convnext_mlp_kernel_matches_xla_block(n, hw, c):
    """The block-MLP kernel (interpreted) against the block's MLP as XLA
    runs it where the kernel does not (``layers.layernorm`` with the mean
    from the channel sums, expansion, ``layers.gelu``, projection, layer
    scale) and the residual add, every parameter drawn; float32 at
    ``highest`` on both sides, so within 1e-6 of the largest output."""
    from repro.kernels.convnext_mlp import convnext_mlp_kernel
    ks = jax.random.split(jax.random.fold_in(KEY, c), 9)
    d = 4 * c
    h = jax.random.normal(ks[0], (n, hw, hw, c)) * 2 + 0.3
    x = jax.random.normal(ks[1], (n, hw, hw, c))
    ln = {"scale": jax.random.uniform(ks[2], (c,), minval=0.5, maxval=1.5),
          "bias": jax.random.normal(ks[3], (c,)) * 0.2}
    w1 = jax.random.normal(ks[4], (c, d)) / c ** 0.5
    b1 = jax.random.normal(ks[5], (d,)) * 0.2
    w2 = jax.random.normal(ks[6], (d, c)) / d ** 0.5
    b2 = jax.random.normal(ks[7], (c,)) * 0.2
    gamma = jax.random.uniform(ks[8], (c,), minval=0.1, maxval=1.0)
    total = jnp.sum(h, axis=-1)
    with jax.default_matmul_precision("highest"):
        t = L.gelu(L.layernorm(ln, h, 1e-6, total) @ w1 + b1)
        ref = x + (t @ w2 + b2) * gamma
    y = convnext_mlp_kernel(h, total, x, ln["scale"], ln["bias"], w1, b1, w2,
                            b2, gamma, eps=1e-6, interpret=True)
    assert y.shape == ref.shape
    assert float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref))) <= 1e-6


def test_maxpool_basic():
    x = jnp.arange(16.0).reshape(1, 4, 4, 1)
    y = L.maxpool2d(x, 2, 2, 0)
    np.testing.assert_array_equal(np.asarray(y)[0, :, :, 0],
                                  [[5, 7], [13, 15]])


def test_batchnorm_folds_stats():
    p = L.init_bn(4, jnp.float32)
    p["mean"] = jnp.full((4,), 2.0)
    p["var"] = jnp.full((4,), 4.0)
    x = jnp.full((1, 2, 2, 4), 6.0)
    # (6-2)/2 = 2
    np.testing.assert_allclose(np.asarray(L.batchnorm(p, x)), 2.0, atol=1e-3)
