"""``BENCHMARK.json`` keeps to its format rules, every name in it finds its
file, and the peaks table refuses a device it does not know."""

import json
import re
import types

import pytest
from chipbench_testlib import ROOT

import spec

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
LINE = re.compile(r"[^\n\t]{1,200}")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
BENCH = spec.Bench()


def test_top_level_keys_and_limits():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(DOC["paths"]) <= 16 and len(DOC["command"]) <= 32
    for p in DOC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p) and ".." not in p
    for word in DOC["command"]:
        assert LINE.fullmatch(word) and not word.startswith("/")
    assert isinstance(DOC["run_seconds"], int) and 1 <= DOC["run_seconds"] <= 51
    assert len(json.dumps(DOC)) < 64 * 1024


def test_names_units_and_lines_use_allowed_characters():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in DOC[k]]
    names += [w[k] for w in DOC["workloads"] for k in ("config", "traffic")]
    names += [r for c in DOC["configs"] for r in c["reduced"]]
    for n in names:
        assert NAME.fullmatch(n), n
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    lines = [e["why"] for k in ("configs", "workloads") for e in DOC[k]]
    lines += [c["source"] for c in DOC["configs"]]
    lines += [m["layer"] for m in DOC["per_layer"]]
    for text in lines:
        assert LINE.fullmatch(text), text


def test_entries_have_exactly_their_keys():
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in DOC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in DOC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


@pytest.mark.parametrize("w", DOC["workloads"], ids=lambda w: w["name"])
def test_each_cell_finds_its_files_and_reports_enough(w):
    cfg = BENCH.config(w["config"])
    assert (BENCH.here / "models" / f"{cfg['model']}.py").exists()
    traffic = BENCH.traffic(w["traffic"])
    BENCH.generator(traffic).validate(traffic)
    e2e = {m["name"] for m in BENCH.end_to_end(w["name"])}
    assert "setup_s" in e2e and len(e2e) >= 2
    for name in e2e - {"setup_s"}:
        assert BENCH.metric_reader(name).read(_run()) > 0
    layer = BENCH.per_layer(w["name"])
    assert layer and all(m["moves"] in e2e for m in layer)
    for m in layer:
        assert callable(BENCH.metric_reader(m["name"]).read)


def test_configs_name_their_files_once_and_reduce_nothing():
    files = [c["file"] for c in DOC["configs"]]
    assert len(set(files)) == len(files)
    for c in DOC["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in DOC["paths"]))
        cfg = BENCH.config(c["name"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"] == []


def test_peaks_known_and_unknown_kinds():
    v5e = BENCH.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        BENCH.peaks("TPU v9 imaginary")


def test_flops_peak_follows_the_configurations_precision():
    cfg = BENCH.config(DOC["configs"][0]["name"])
    assert cfg["dtype"] == "float32" and cfg["matmul_precision"] == "highest"
    # float32 at highest is six bfloat16 passes of the matrix unit
    assert BENCH.flops_peak("TPU v5 lite", cfg) == pytest.approx(197e12 / 6)
    assert BENCH.flops_peak("TPU v5 lite", dict(cfg, dtype="bfloat16")) == 197e12


def _run():
    gen = BENCH.generator({"generator": "closed_loop"})
    w = gen.Window()
    w.images, w.seconds = 10, 2.0
    w.latencies = [i / 1000 for i in range(100, 0, -1)]
    return types.SimpleNamespace(window=w)


def test_loadgen_end_to_end_arithmetic():
    run = _run()
    assert BENCH.metric_reader("images_per_s").read(run) == 5.0
    # nearest rank: the 90th of 100 sorted latencies
    assert BENCH.metric_reader("latency_p90_ms").read(run) == pytest.approx(90.0)
    with pytest.raises(FileNotFoundError):
        BENCH.metric_reader("tokens_per_s")
