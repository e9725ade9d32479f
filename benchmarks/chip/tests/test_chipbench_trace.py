"""The trace reduction on two small real traces, recorded on a TPU v5e by
``run.run_cell`` with ``--trace 1`` and a window of a few queries
(``data/``, gzipped ``.xplane.pb``): four offline batches of 128 images
through ``forward_fused_groups``, and two single-image queries through
``forward``."""

import gzip

import jax
import numpy as np
import pytest
from chipbench_testlib import BENCH

import devtrace

DATA = BENCH / "tests" / "data"


def load(name: str) -> devtrace.Reduced:
    raw = gzip.decompress((DATA / f"{name}_tpu_v5e.xplane.pb.gz").read_bytes())
    return devtrace.reduce_profile(jax.profiler.ProfileData.from_serialized_xspace(raw))


@pytest.fixture(scope="module", params=["offline", "singlestream"])
def trace(request):
    return request.param, load(request.param)


def test_one_tpu_device_and_the_benchmarks_spans(trace):
    name, r = trace
    assert list(r.ops) == ["/device:TPU:0"]
    kinds = {s for s, _, _ in r.spans}
    want = {"query.call", "query.wait"} if name == "offline" \
        else {"query.put", "query.call", "query.fetch"}
    assert kinds == want
    assert r.t0 == min(s for _, s, _ in r.spans)
    assert r.t1 == max(e for _, _, e in r.spans)


def test_busy_is_the_union_of_op_intervals_in_the_window(trace):
    _, r = trace
    # independently: mark every nanosecond an operation covers
    t0 = int(np.floor(r.t0))
    covered = np.zeros(int(np.ceil(r.t1)) - t0 + 1, bool)
    n = 0
    for _, s, e in r.ops["/device:TPU:0"]:
        s, e = max(s, r.t0), min(e, r.t1)
        if e > s:
            covered[int(round(s)) - t0:int(round(e)) - t0] = True
            n += 1
    assert r.busy_s == pytest.approx(covered.sum() / 1e9, abs=2e-9 * n)
    assert r.idle_share == pytest.approx(1 - r.busy_s / r.window_s)


def test_idle_gaps_add_up_and_name_host_spans(trace):
    name, r = trace
    gaps = r.idle_gaps()
    assert sum(sec for _, sec in gaps) == pytest.approx(r.window_s - r.busy_s)
    assert all(label.startswith(devtrace.SPAN_PREFIX) or label == devtrace.NO_SPAN
               for label, _ in gaps)
    # a single query waits on its answer: the host is in the fetch
    if name == "singlestream":
        assert gaps[0][0] == "query.fetch"
        assert r.idle_share > 0.9


def test_offline_group1_ops_found_by_output_shape():
    r = load("offline")
    assert devtrace.parse_op(
        "%fusion.11 = f32[128,112,112,64]{0,3,2,1:T(8,128)} fusion(%x.1)") \
        == ("fusion.11", (128, 112, 112, 64))
    g1 = r.op_seconds({112, 56})
    # stem conv, max-pool and stage 1 carry most of the device time
    assert 0.4 < g1 / r.op_seconds() < 0.8
    assert r.top_ops()[0][0] == "fusion.11 [128, 112, 112, 64]"
    assert r.idle_share < 0.1


def test_merge_and_rows_helpers():
    assert devtrace.merged([(5, 6), (0, 2), (1, 3), (5.5, 5.7)]) \
        == [(0, 3), (5, 6)]
    assert devtrace.has_rows((128, 56, 56, 64), {56})
    assert not devtrace.has_rows((128, 28, 28, 128), {112, 56})
    assert devtrace.parse_op("convolution.4") == ("convolution.4", ())
