"""The benchmark's own FLOP and byte arithmetic (``flops.py`` and the
adapter's ``layers``), against the shapes of
``repro.core.graph.build_resnet18``."""

import json

import pytest
from chipbench_testlib import BENCH

from models import cnn_classifier as M
from repro.core.graph import OpKind, build_resnet18

CONFIGS = sorted((BENCH / "configs").glob("resnet18-*.json"))


@pytest.fixture(scope="module", params=CONFIGS, ids=lambda p: p.stem)
def cfg(request):
    return json.loads(request.param.read_text())


def test_resnet18_flops_per_image(cfg):
    # 1.814 GMAC per 224x224 image (He et al. 2016, Table 1: 1.8e9 FLOPs
    # counted as multiply-adds), twice for FLOPs
    assert M.flops_per_input(cfg) == pytest.approx(3.628e9, rel=1e-3)


def test_layers_agree_with_core_graph(cfg):
    graph = build_resnet18(cfg["image_size"], cfg["num_classes"])
    assert 2 * graph.total_macs == M.flops_per_input(cfg)
    want = [(g.cin, g.cout, g.kh, g.stride, g.iy, g.oy)
            for g in graph.layers if g.kind.is_conv]
    got = [(lyr.cin, lyr.cout, lyr.k, lyr.stride, lyr.in_hw, lyr.out_hw)
           for lyr in M.layers(cfg) if lyr.kind == "conv"]
    assert sorted(got) == sorted(want)
    taps = sum(g.weight_elems - 2 * g.cout for g in graph.layers
               if g.kind.is_conv)
    fc = next(g for g in graph.layers if g.kind is OpKind.FC)
    # 11.68 M conv and fc weights; the benchmark adds four BN vectors/conv
    assert taps + fc.weight_elems == pytest.approx(11.69e6, rel=1e-3)
    bn = sum(4 * lyr.cout for lyr in M.layers(cfg) if lyr.kind == "conv")
    assert sum(lyr.weights for lyr in M.layers(cfg)) \
        == taps + fc.weight_elems + bn


def test_group1_work(cfg):
    # stem conv, max-pool and stage 1 of the paper's Fused4 grouping
    work = M.group_work(cfg, "group1", 128)
    assert work["rows"] == {112, 56}
    assert work["flops"] == 128 * 2 * (118013952 + 4 * 115605504)
    # input 224x224x3, output 56x56x64, float32, plus weights
    want = 4 * (128 * (224 * 224 * 3 + 56 * 56 * 64)
                + 7 * 7 * 3 * 64 + 4 * 3 * 3 * 64 * 64 + 5 * 4 * 64)
    assert work["bytes"] == want
    groups = {lyr.group for lyr in M.layers(cfg)}
    assert sum(M.group_work(cfg, g, 1)["flops"] for g in groups) \
        == M.flops_per_input(cfg)
