"""The benchmark's plain reference against the program, on the CPU at
32x32 images and ResNet18's published widths, with batch-norm statistics
drawn from the seed."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_testlib import BENCH, no_batchnorm

from models import cnn_classifier as M
from repro.models.resnet import forward, forward_fused_groups, init_resnet18

LIMIT = json.loads((BENCH / "configs" / "resnet18-fused4.json").read_text()
                   )["check_limits"]["logit_err"]


@pytest.fixture(scope="module")
def case():
    cfg = json.loads((BENCH / "configs" / "resnet18-layerwise.json").read_text())
    cfg.update(image_size=32)
    key = jax.random.key(2**31 + 11)
    p = jax.jit(lambda k: M.init_params(cfg, k))(key)
    x = M.init_inputs(cfg, jax.random.fold_in(key, 1), 2)
    ref = np.asarray(jax.jit(lambda p, x: M.reference(cfg, p, x))(p, x))
    return cfg, p, x, ref


def err(y, ref) -> float:
    return float(np.abs(np.asarray(y, np.float64) - ref).max()
                 / np.abs(ref).max())


def test_params_have_the_programs_layout_and_no_identity(case):
    cfg, p, _, _ = case
    assert jax.tree.structure(p) == jax.tree.structure(
        init_resnet18(jax.random.key(0)))
    for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
        name = jax.tree_util.keystr(path)
        if name.endswith(("['mean']", "['bias']", "['fc_b']")):
            assert float(jnp.std(leaf)) > 0.05, name
        if name.endswith(("['var']", "['scale']")):
            assert float(jnp.abs(leaf - 1).max()) > 0.2, name


@pytest.mark.parametrize("fn", [forward, forward_fused_groups],
                         ids=["layerwise", "fused4"])
def test_reference_matches_program(case, fn):
    _, p, x, ref = case
    assert err(jax.jit(fn)(p, x), ref) < 1e-5


def test_program_without_batchnorm_fails(case):
    _, p, x, ref = case
    assert err(jax.jit(no_batchnorm)(p, x), ref) > 10 * LIMIT


def test_control_reads_above_the_limit(case):
    cfg, p, x, ref = case
    assert err(jax.jit(lambda p, x: M.control(cfg, p, x))(p, x), ref) > LIMIT


def test_checks_refuse_another_dtype(case):
    cfg, p, x, ref = case
    y = np.asarray(jax.jit(forward)(p, x))
    ok = M.checks(cfg, y, ref)
    assert all(c["value"] <= c["limit"] for c in ok.values())
    low = M.checks(cfg, np.asarray(jnp.asarray(y, jnp.bfloat16)), ref)
    assert low["dtype"]["value"] > low["dtype"]["limit"]
