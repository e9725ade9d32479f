"""The ConvNeXt adapter against the program, on the CPU at 32x32 images and
ConvNeXt-T's published widths and depths, with every bias, LayerNorm and
layer scale drawn from the seed: the reference, the broken programs it
refuses, its work arithmetic, the readers of the ``dwconv`` and ``mlp``
scopes, and a whole run of the cell."""

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
from models import convnext_classifier as M
from repro.models import convnext, layers

CFG = json.loads((BENCH / "configs" / "convnext-tiny.json").read_text())
LIMIT = CFG["check_limits"]["logit_err"]
CELL = "convnext-tiny.offline"


@pytest.fixture(scope="module")
def case():
    cfg = dict(CFG, image_size=32)
    key = jax.random.key(2**31 + 17)
    p = jax.jit(lambda k: M.init_params(cfg, k))(key)
    x = M.init_inputs(cfg, jax.random.fold_in(key, 1), 2)
    ref = np.asarray(jax.jit(lambda p, x: M.reference(cfg, p, x))(p, x))
    return cfg, p, x, ref


def err(y, ref) -> float:
    return float(np.abs(np.asarray(y, np.float64) - ref).max()
                 / np.abs(ref).max())


def test_params_have_the_programs_layout_and_nothing_constant(case):
    _, p, _, _ = case
    assert jax.tree.structure(p) == jax.tree.structure(
        jax.eval_shape(convnext.init_convnext_tiny, jax.random.key(0)))
    drawn = ("['scale']", "['bias']", "['b']", "['dw_b']", "['b1']", "['b2']",
             "['fc_b']", "['gamma']")
    n = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
        name = jax.tree_util.keystr(path)
        if name.endswith(drawn):
            assert float(jnp.std(leaf)) > 0.05, name
            n += 1
        if name.endswith("['gamma']"):
            assert float(leaf.min()) >= 0.1, name
    # per block 6 (dw_b, LN scale and bias, b1, b2, gamma); per downsample
    # 3; the stem 3; the head 3
    assert n == 6 * sum(CFG["depths"]) + 3 * (len(CFG["depths"]) - 1) + 6


def test_reference_matches_program(case):
    _, p, x, ref = case
    assert err(jax.jit(convnext.forward)(p, x), ref) < 1e-5


def test_control_reads_above_the_limit(case):
    cfg, p, x, ref = case
    assert err(jax.jit(lambda p, x: M.control(cfg, p, x))(p, x), ref) > LIMIT


def _tanh_gelu(p, x, monkeypatch):
    monkeypatch.setattr(layers, "gelu",
                        lambda t: jax.nn.gelu(t, approximate=True))
    return convnext.forward(p, x)


def _block_skipped(p, x, _monkeypatch):
    """The fifth block of stage 3 left out: its branch adds nothing."""
    stages = [dict(s) for s in p["stages"]]
    stages[2]["blocks"] = stages[2]["blocks"][:4] + stages[2]["blocks"][5:]
    return convnext.forward(dict(p, stages=stages), x)


def _depthwise_flipped(p, x, _monkeypatch):
    """Every depthwise kernel applied turned by 180 degrees: a convolution
    where the layer is a cross-correlation."""
    stages = [dict(s, blocks=[dict(b, dw_w=b["dw_w"][::-1, ::-1])
                              for b in s["blocks"]]) for s in p["stages"]]
    return convnext.forward(dict(p, stages=stages), x)


@pytest.mark.parametrize("broken", [_tanh_gelu, _block_skipped,
                                    _depthwise_flipped],
                         ids=["tanh-gelu", "block-skipped", "dwconv-flipped"])
def test_broken_program_reads_ten_times_over_the_limit(case, broken,
                                                       monkeypatch):
    _, p, x, ref = case
    y = jax.jit(lambda p, x: broken(p, x, monkeypatch))(p, x)
    assert err(y, ref) > 10 * LIMIT


def test_checks_refuse_bfloat16(case):
    cfg, p, x, ref = case
    y = np.asarray(jax.jit(convnext.forward)(p, x))
    ok = M.checks(cfg, y, ref)
    assert all(c["value"] <= c["limit"] for c in ok.values())
    low = M.checks(cfg, np.asarray(jnp.asarray(y, jnp.bfloat16)), ref)
    assert low["dtype"]["value"] > low["dtype"]["limit"]


# --- work ---------------------------------------------------------------------

def test_work_at_published_sizes():
    params = jax.eval_shape(lambda k: M.init_params(CFG, k), jax.random.key(0))
    n_params = sum(leaf.size for leaf in jax.tree.leaves(params))
    assert n_params == sum(q["weights"] for q in M.parts(CFG)) == 28_589_128
    # 4.456 G multiply-adds per 224x224 image
    assert M.flops_per_input(CFG) == 2 * 4_455_531_264
    batch = 128
    assert sum(M.group_work(CFG, g, batch)["flops"] for g in CFG["groups"]) \
        == batch * M.flops_per_input(CFG)
    assert M.group_work(CFG, "group1", batch)["rows"] == {56}
    peak, hbm = 197e12 / 6, 819e9
    dw, mlp = (M.kind_work(CFG, k, batch) for k in M.KINDS)
    # the depthwise convs are bound by bytes, the MLPs by FLOPs
    assert dw["bytes"] / hbm > 3 * dw["flops"] / peak
    assert mlp["flops"] / peak > 10 * mlp["bytes"] / hbm
    assert mlp["flops"] == pytest.approx(1.065e12, rel=1e-3)
    with pytest.raises(KeyError):
        M.kind_work(CFG, "attention", batch)


# --- the readers of the block's scopes ----------------------------------------

@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "use_compile_cache", lambda: None)
        yield small_bench(tmp_path_factory.mktemp("bench"))


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


@pytest.mark.parametrize("kind", ["dwconv", "mlp"])
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
    reader = spec.Bench().metric_reader(f"{kind}_roofline.convnext")
    assert reader.read(run) == pytest.approx(want)


def test_kind_reader_reads_none_where_the_map_does_not_fit(compiled,
                                                           monkeypatch):
    run, ops = compiled
    name = next(iter(ops))
    monkeypatch.setattr(kinds, "kind_map",
                        lambda _run: {k: v for k, v in ops.items() if k != name})
    assert kinds.kind_roofline(run, "dwconv") is None
    monkeypatch.setattr(kinds, "kind_map", lambda _run: ops)
    empty = types.SimpleNamespace(**vars(run))
    empty.trace = types.SimpleNamespace(ops={})
    assert kinds.kind_roofline(empty, "mlp") is None


# --- a whole run ----------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_cell_gives_a_correct_result_line(bench, trace):
    r = harness.run_cell(bench, CELL, 2**31 + 5, 0.2, bool(trace),
                         platforms=("cpu",))
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    # the CPU has no device plane: only host-clock metrics can be read
    want = {"mfu.convnext"} if trace else {"images_per_s", "setup_s"}
    assert set(r["metrics"]) == want
    assert r["checks"]["logit_err"]["value"] < LIMIT
