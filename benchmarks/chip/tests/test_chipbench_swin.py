"""The Swin adapter against the program, on the CPU at Swin-T's published
widths, depths and heads, with every bias, LayerNorm and bias table drawn
from the seed: at 64x64 images with 4x4 windows (maps 16/8/4/2, so stages
3 and 4 take the rule for a map no larger than its window) and at 224x224
with 7x7 windows.  The reference, the broken programs it refuses, its
work arithmetic, the readers of the ``attn`` and ``mlp`` scopes, and a
whole run of the cell."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_testlib import BENCH, small_bench

import kinds
import run as harness
import scopes
import spec
from models import swin_classifier as M
from repro.models import layers, swin

CFG = json.loads((BENCH / "configs" / "swin-tiny.json").read_text())
LIMIT = CFG["check_limits"]["logit_err"]
CELL = "swin-tiny.offline"
# (image size, window, batch)
SIZES = {"64-w4": (64, 4, 2), "224-w7": (224, 7, 1)}


@pytest.fixture(scope="module", params=list(SIZES))
def case(request):
    image, window, batch = SIZES[request.param]
    cfg = dict(CFG, image_size=image, window_size=window)
    key = jax.random.key(2**31 + 17)
    p = jax.jit(lambda k: M.init_params(cfg, k))(key)
    x = M.init_inputs(cfg, jax.random.fold_in(key, 1), batch)
    ref = np.asarray(jax.jit(lambda p, x: M.reference(cfg, p, x))(p, x))
    return cfg, p, x, ref


def err(y, ref) -> float:
    return float(np.abs(np.asarray(y, np.float64) - ref).max()
                 / np.abs(ref).max())


def program(p, x):
    with jax.default_matmul_precision("highest"):
        return swin.forward(p, x)


def test_params_have_the_programs_layout_and_nothing_constant(case):
    cfg, p, _, _ = case
    assert jax.tree.structure(p) == jax.tree.structure(
        jax.eval_shape(swin.init_swin_tiny, jax.random.key(0)))
    for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
        assert float(jnp.std(leaf)) > 0.01, jax.tree_util.keystr(path)
    # each table has a row per offset of its stage's window
    hw = cfg["image_size"] // cfg["patch_size"]
    for si, s in enumerate(p["stages"]):
        w = min(hw >> si, cfg["window_size"])
        for b in s["blocks"]:
            assert b["table"].shape == ((2 * w - 1) ** 2, cfg["heads"][si])


def test_reference_matches_program(case):
    _, p, x, ref = case
    assert err(jax.jit(program)(p, x), ref) < LIMIT / 2


def test_control_reads_above_the_limit(case):
    cfg, p, x, ref = case
    assert err(jax.jit(lambda p, x: M.control(cfg, p, x))(p, x), ref) > LIMIT


def _tanh_gelu(monkeypatch):
    monkeypatch.setattr(layers, "gelu",
                        lambda t: jax.nn.gelu(t, approximate=True))


def _shift_off(monkeypatch):
    attend = swin.window_attention
    monkeypatch.setattr(swin, "window_attention",
                        lambda p, x, w, _shift: attend(p, x, w, 0))


def _mask_off(monkeypatch):
    mask = swin.shift_mask
    monkeypatch.setattr(swin, "shift_mask",
                        lambda hw, w, s: np.zeros_like(mask(hw, w, s)))


def _bias_index_transposed(monkeypatch):
    """Each pair reads the table at key − query, not query − key."""
    index = swin.relative_index
    monkeypatch.setattr(swin, "relative_index", lambda w: index(w).T)


def _merge_order_swapped(monkeypatch):
    """The patch merging's phases concatenated column phase fastest:
    x[0::2, 0::2], x[0::2, 1::2], x[1::2, 0::2], x[1::2, 1::2]."""
    def merge(p, x):
        b, h, wd, c = x.shape
        x = x.reshape(b, h // 2, 2, wd // 2, 2, c).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(b, h // 2, wd // 2, 4 * c)
        return layers.layernorm(p["ln"], x, swin.LN_EPS) @ p["w"]
    monkeypatch.setattr(swin, "patch_merge", merge)


@pytest.mark.parametrize("broken", [
    _tanh_gelu, _shift_off, _mask_off, _bias_index_transposed,
    _merge_order_swapped],
    ids=["tanh-gelu", "shift-off", "mask-off", "bias-index-transposed",
         "merge-order-swapped"])
def test_broken_program_reads_ten_times_over_the_limit(case, broken,
                                                       monkeypatch):
    _, p, x, ref = case
    broken(monkeypatch)
    # a new function, so that jit traces the broken program afresh
    assert err(jax.jit(lambda p, x: program(p, x))(p, x), ref) > 10 * LIMIT


def test_checks_refuse_bfloat16(case):
    cfg, p, x, ref = case
    y = np.asarray(jax.jit(program)(p, x))
    ok = M.checks(cfg, y, ref)
    assert all(c["value"] <= c["limit"] for c in ok.values())
    low = M.checks(cfg, np.asarray(jnp.asarray(y, jnp.bfloat16)), ref)
    assert low["dtype"]["value"] > low["dtype"]["limit"]


# --- work ---------------------------------------------------------------------

def test_work_at_published_sizes():
    params = jax.eval_shape(lambda k: M.init_params(CFG, k), jax.random.key(0))
    n_params = sum(leaf.size for leaf in jax.tree.leaves(params))
    assert n_params == sum(q["weights"] for q in M.parts(CFG)) == 28_288_354
    program_params = jax.eval_shape(swin.init_swin_tiny, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(program_params)) == n_params
    # 4.49 G multiply-adds per 224x224 image
    assert M.flops_per_input(CFG) == 2 * 4_490_566_656
    batch = 128
    assert sum(M.group_work(CFG, g, batch)["flops"] for g in CFG["groups"]) \
        == batch * M.flops_per_input(CFG)
    assert M.group_work(CFG, "group1", batch)["rows"] == {56}
    peak, hbm = 197e12 / 6, 819e9
    attn, mlp = (M.kind_work(CFG, k, batch) for k in M.KINDS)
    # both are bound by FLOPs: 11.9 and 21.6 ms per batch at highest
    assert attn["flops"] / peak == pytest.approx(11.91e-3, rel=1e-3)
    assert attn["flops"] / peak > 5 * attn["bytes"] / hbm
    assert mlp["flops"] / peak == pytest.approx(21.63e-3, rel=1e-3)
    with pytest.raises(KeyError):
        M.kind_work(CFG, "dwconv", batch)


# --- the readers of the block's scopes ----------------------------------------

@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The small benchmark copy, with Swin-T at 64x64 images and 4x4
    windows: its 32x32 images would leave 8x8 maps that 7x7 windows do not
    tile."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "use_compile_cache", lambda: None)
        b = small_bench(tmp_path_factory.mktemp("bench"))
        path = next(b.root / c["file"] for c in b.doc["configs"]
                    if c["name"] == b.workload(CELL)["config"])
        cfg = json.loads(path.read_text())
        path.write_text(json.dumps(dict(cfg, image_size=64, window_size=4)))
        yield b


@pytest.fixture(scope="module")
def compiled(bench):
    """The cell's program at a small size, compiled on the CPU as the
    readers compile it, and a run whose trace holds every ENTRY
    instruction of it, each taking one microsecond."""
    w = bench.workload(CELL)
    cfg = bench.config(w["config"])
    traffic = bench.traffic(w["traffic"])
    adapter = bench.adapter(cfg["model"])
    text = scopes.program_text(cfg, adapter, traffic["batch"])
    body = text[text.index("\n", text.index("\nENTRY ") + 1) + 1:]
    lines = body[:body.index("\n}\n")].splitlines()
    trace = types.SimpleNamespace(ops={"/device:TPU:0": [
        (line.strip(), 1000 * i, 1000 * i + 1000)
        for i, line in enumerate(lines)]})
    run = types.SimpleNamespace(
        cfg=cfg, adapter=adapter, traffic=traffic, trace=trace,
        peaks=bench.peaks("TPU v5 lite"),
        flops_peak=bench.flops_peak("TPU v5 lite", cfg),
        traced=types.SimpleNamespace(attempted=3))
    return run, scopes.entry_map(text, M.KINDS)


@pytest.mark.parametrize("kind", ["attn", "mlp"])
def test_kind_reader_reads_its_scope(compiled, kind, monkeypatch):
    run, ops = compiled
    monkeypatch.setattr(kinds, "_MAPS", {})
    assert kinds.kind_map(run) == ops
    n = sum(1 for _, k in ops.values() if k == kind)
    assert n > 0
    assert kinds.kind_seconds(run, kind) == pytest.approx(n * 1e-6)
    work = M.kind_work(run.cfg, kind, run.traffic["batch"])
    least = max(work["flops"] / run.flops_peak,
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    want = 100.0 * least * 3 / (n * 1e-6)
    reader = spec.Bench().metric_reader(f"{kind}_roofline.swin")
    assert reader.read(run) == pytest.approx(want)


@pytest.mark.parametrize("kind", ["attn", "mlp"])
def test_kind_reader_reads_none_where_the_map_does_not_fit(compiled, kind,
                                                           monkeypatch):
    run, ops = compiled
    reader = spec.Bench().metric_reader(f"{kind}_roofline.swin")
    name = next(iter(ops))
    monkeypatch.setattr(kinds, "kind_map",
                        lambda _run: {k: v for k, v in ops.items() if k != name})
    assert reader.read(run) is None
    monkeypatch.setattr(kinds, "kind_map", lambda _run: ops)
    empty = types.SimpleNamespace(**vars(run))
    empty.trace = types.SimpleNamespace(ops={})
    assert reader.read(empty) is None


# --- a whole run ----------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_cell_gives_a_correct_result_line(bench, trace):
    r = harness.run_cell(bench, CELL, 2**31 + 5, 0.2, bool(trace),
                         platforms=("cpu",))
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    # the CPU has no device plane: only host-clock metrics can be read
    want = {"mfu.swin"} if trace else {"images_per_s", "setup_s"}
    assert set(r["metrics"]) == want
    assert r["checks"]["logit_err"]["value"] < LIMIT
