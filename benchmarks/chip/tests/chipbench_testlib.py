"""Helpers for the chip benchmark's CPU tests: a copy of the benchmark at a
small size, and broken forms of the timed path (each a configuration
``entry`` the harness imports by name).

The copy keeps every width relation of ResNet18 but at 32x32 images,
narrow stages and ten classes, so that a whole run compiles and finishes
in seconds on the CPU.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import jax.numpy as jnp

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import spec  # noqa: E402

SMALL = {"image_size": 32, "stage_channels": [8, 16, 32, 64],
         "stem_channels": 8, "num_classes": 10}


def small_bench(tmp: Path, **traffic_over) -> spec.Bench:
    """A copy of ``BENCHMARK.json`` and ``benchmarks/chip`` under ``tmp``,
    at a small size, with a ``cpu`` row in the peaks table."""
    here = tmp / "benchmarks" / "chip"
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    for c in doc["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(SMALL)
        (tmp / c["file"]).write_text(json.dumps(cfg))
    for f in (here / "traffic").glob("*.json"):
        t = json.loads(f.read_text())
        t.update(batch=min(t["batch"], 4), pool=2,
                 check_queries=min(t["check_queries"], 4), trace_seconds=0.2)
        t.update(traffic_over)
        f.write_text(json.dumps(t))
    peaks = json.loads((here / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (here / "peaks.json").write_text(json.dumps(peaks))
    return spec.Bench(tmp)


# --- the timed path, broken ---------------------------------------------

def _forward(p, x):
    from repro.models.resnet import forward
    return forward(p, x)


def answer_altered(p, x):
    """The first answer of every batch comes out reversed."""
    y = _forward(p, x)
    return y.at[0].set(y[0, ::-1])


def half_batch(p, x):
    """Only the first half of the batch is computed; the rest repeats it."""
    h = max(1, x.shape[0] // 2)
    y = _forward(p, x[:h])
    return jnp.concatenate([y, y])[:x.shape[0]]


def no_batchnorm(p, x):
    """The program with every batch-norm left out."""
    from repro.models import layers
    keep = layers.batchnorm
    layers.batchnorm = lambda _p, t, eps=1e-5: t
    try:
        return _forward(p, x)
    finally:
        layers.batchnorm = keep
