"""A run whose timed path is broken underneath reads ``correct: false``.

Each fault the cells can have is put in the program's place through the
harness's ``entry`` and driven through a whole run on the CPU at a small
size: an answer altered where it is produced, half of a batch left out,
the batch-norms left out, and the control (the plain reference at
``high``, three bfloat16 passes).  A one-image query has no half to
leave out, so that fault is for the batched cell only.  The cells run on
one chip and have no state, so no exchange between chips and no step
state can be dropped.
"""

from functools import partial

import pytest
from chipbench_testlib import answer_altered, half_batch, no_batchnorm, small_bench

import run
from models import cnn_classifier

OFFLINE = "resnet18-fused4.offline"
SINGLE = "resnet18-layerwise.singlestream"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "use_compile_cache", lambda: None)
        yield small_bench(tmp_path_factory.mktemp("bench"))


def _control(bench, workload):
    cfg = bench.config(bench.workload(workload)["config"])
    return partial(cnn_classifier.control, cfg)


@pytest.mark.parametrize("workload,fault", [
    (OFFLINE, answer_altered), (OFFLINE, half_batch), (OFFLINE, no_batchnorm),
    (OFFLINE, "control"),
    (SINGLE, answer_altered), (SINGLE, no_batchnorm), (SINGLE, "control"),
], ids=lambda v: getattr(v, "__name__", v))
def test_broken_path_is_not_correct(bench, workload, fault):
    entry = _control(bench, workload) if fault == "control" else fault
    r = run.run_cell(bench, workload, 2**31 + 21, 0.2, False,
                     platforms=("cpu",), entry=entry)
    assert r["correct"] is False
    c = r["checks"]["logit_err"]
    assert c["value"] > c["limit"]
