"""Whole runs of the harness on the CPU, at a small size: the result line,
the refusal of a CPU device, and new cells found from files alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest
from chipbench_testlib import BENCH, ROOT, small_bench

import run

CELLS = ["resnet18-fused4.offline", "resnet18-layerwise.singlestream"]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "use_compile_cache", lambda: None)
        yield small_bench(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
@pytest.mark.parametrize("workload", CELLS)
def test_run_gives_a_correct_result_line(bench, workload, trace):
    r = run.run_cell(bench, workload, 2**31 + 3, 0.2, bool(trace),
                     platforms=("cpu",))
    assert list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    names = ({m["name"] for m in bench.per_layer(workload)} if trace
             else {m["name"] for m in bench.end_to_end(workload)})
    # the CPU has no device plane: only host-clock metrics can be read
    want = {n for n in names if not trace or n.startswith("mfu.")}
    assert set(r["metrics"]) == want
    assert all(m["value"] > 0 for m in r["metrics"].values())
    if trace:
        assert {"busy_s", "window_s"} <= set(r["device"])
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert r["checks"]["logit_err"]["value"] < 1e-5


def test_same_seed_same_inputs_and_sample(bench):
    a, b = (run.run_cell(bench, CELLS[1], 5, 0.2, False, platforms=("cpu",))
            for _ in range(2))
    assert a["checks"]["logit_err"] == b["checks"]["logit_err"]


def _bare_copy(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return tmp_path


@pytest.mark.parametrize("where", ["repo", "bare"])
def test_cpu_is_refused_with_no_result(tmp_path, where):
    root = ROOT if where == "repo" else _bare_copy(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2, p.stderr
    assert "{" not in p.stdout
    assert "no device" in p.stderr


def test_new_config_traffic_and_metric_found_without_edit(tmp_path):
    bench = small_bench(tmp_path)
    here = bench.here
    cfg = json.loads((here / "configs" / "resnet18-layerwise.json").read_text())
    cfg["name"] = "resnet18-new"
    (here / "configs" / "resnet18-new.json").write_text(json.dumps(cfg))
    t = json.loads((here / "traffic" / "offline.json").read_text())
    t.update(batch=2, in_flight=3, generator="burst_loop")
    (here / "traffic" / "burst.json").write_text(json.dumps(t))
    shutil.copy(here / "generators" / "closed_loop.py",
                here / "generators" / "burst_loop.py")
    (here / "metrics" / "images_seen.py").write_text(
        "def read(run):\n    return float(run.window.images)\n")
    (here / "metrics" / "queries_per_s.py").write_text(
        "def read(run):\n    return run.window.attempted / run.window.seconds\n")
    doc = bench.doc
    doc["configs"].append({"name": "resnet18-new", "source": "x",
                           "file": "benchmarks/chip/configs/resnet18-new.json",
                           "reduced": [], "why": "x"})
    doc["workloads"].append({"name": "resnet18-new.burst",
                             "config": "resnet18-new", "traffic": "burst",
                             "chips": 1, "why": "x"})
    doc["end_to_end"][0]["workloads"].append("resnet18-new.burst")
    doc["end_to_end"].append({"name": "queries_per_s", "unit": "queries/s",
                              "better": "higher", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["resnet18-new.burst"]})
    doc["per_layer"] += [
        {"name": "images_seen", "unit": "images", "better": "higher",
         "source": "host_clock", "layer": "x", "moves": "images_per_s",
         "workloads": ["resnet18-new.burst"]},
        {"name": "mfu.burst", "unit": "%", "better": "higher",
         "source": "host_clock", "layer": "x", "moves": "images_per_s",
         "workloads": ["resnet18-new.burst"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    new = type(bench)(tmp_path)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "use_compile_cache", lambda: None)
        r = run.run_cell(new, "resnet18-new.burst", 9, 0.2, True,
                         platforms=("cpu",))
        e = run.run_cell(new, "resnet18-new.burst", 9, 0.2, False,
                         platforms=("cpu",))
    assert r["correct"] is True and e["correct"] is True
    assert set(r["metrics"]) == {"images_seen", "mfu.burst"}
    assert r["metrics"]["images_seen"]["value"] > 0
    assert set(e["metrics"]) == {"images_per_s", "queries_per_s", "setup_s"}
    assert e["metrics"]["queries_per_s"]["value"] > 0
