"""Where the benchmark finds its parts, by the names in ``BENCHMARK.json``.

Nothing here names a configuration, traffic mix or metric: each is a file
that a later change adds beside the others.

* a configuration: the ``file`` its ``configs`` entry gives (JSON sizes),
  with ``model`` naming its adapter, ``models/<model>.py``, and ``entry``
  the program's function (``module:function``);
* an adapter defines ``init_params(cfg, key)``, ``init_inputs(cfg, key,
  n)`` (on the device), ``host_inputs(cfg, seed_words, n)``,
  ``reference(cfg, params, x)``, ``control(cfg, params, x)``,
  ``checks(cfg, answers, reference_answers)`` (each compared number with
  its limit), ``flops_per_input(cfg)`` and ``group_work(cfg, group,
  batch)``;
* a traffic mix: ``traffic/<traffic>.json``, parameters of the generator
  it names, ``generators/<generator>.py`` (``validate(traffic)``,
  ``run_window(...)``);
* a metric ``<family>.<split>``, end-to-end or per-layer (all but
  ``setup_s``): ``metrics/<family>.<split>.py`` if there is one, else
  ``metrics/<family>.py``; it defines ``read(run) -> float | None``;
* peaks: ``peaks.json``, keyed by JAX's ``device_kind``.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path = ROOT, here: Path | None = None) -> None:
        self.root = Path(root)
        self.here = Path(here) if here else self.root / "benchmarks" / "chip"
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())
        self.adapters: dict = {}
        self.programs: dict = {}    # jitted set-up and reference programs

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.here / "traffic" / f"{name}.json").read_text())

    def generator(self, traffic: dict):
        name = traffic["generator"]
        return load_module(self.here / "generators" / f"{name}.py",
                           f"chipbench_generator_{name}")

    def adapter(self, model: str):
        if model not in self.adapters:
            self.adapters[model] = load_module(
                self.here / "models" / f"{model}.py", f"chipbench_model_{model}")
        return self.adapters[model]

    def end_to_end(self, workload: str) -> list[dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.doc["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list[dict]:
        """The per-layer metrics read in this cell's traced run."""
        moved = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.doc["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]

    def metric_reader(self, name: str):
        d = self.here / "metrics"
        path = d / f"{name}.py"
        if not path.exists():
            path = d / f"{name.split('.')[0]}.py"
        return load_module(path, "chipbench_metric_" + re.sub(r"\W", "_", name))

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.here / "peaks.json").read_text())["devices"]
        if device_kind not in table:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           f"peaks.json")
        return table[device_kind]

    def flops_peak(self, device_kind: str, cfg: dict) -> float:
        """FLOP/s the chip peaks at in the configuration's dtype and
        matmul precision: float32 runs as 1, 3 or 6 bfloat16 passes."""
        peak = self.peaks(device_kind)["bf16_flops"]
        if cfg["dtype"] == "bfloat16":
            return peak
        table = json.loads((self.here / "peaks.json").read_text())
        return peak / table["float32_passes"][cfg["matmul_precision"]]
