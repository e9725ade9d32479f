#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix; ``spec.py`` says where each is found.  Set-up makes the
parameters and inputs on the device from ``--seed``, compiles and warms
the cell's one program (the configuration's entry point, traced at its
``matmul_precision``), and is reported as ``setup_s``.  The window then
drives that program through the traffic's generator for ``--seconds``
(``--trace 1``: for the traffic's ``trace_seconds``, then as long again
under the profiler).  Afterwards a sample of the window's answers, drawn
from the seed, is compared with the adapter's plain reference by the
adapter's ``checks``; ``correct`` says whether every compared number is
within its limit.

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (``--trace 0``: the cell's end-to-end metrics;
``--trace 1``: its per-layer metrics), ``device`` and, traced,
``breakdown``; the last key, ``checks``, holds each compared number with
its limit, which are also the last lines on stderr.  Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from functools import partial  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import devtrace  # noqa: E402
import spec  # noqa: E402

CACHE_DIR = spec.ROOT / ".jax_cache"
REF_BLOCK = 32          # images per reference call


class NoDevice(RuntimeError):
    """JAX found no accelerator of the kind, or too few of them."""


class CompileCount:
    """Compilations (traces, lowerings, backend compiles, cache lookups)
    that JAX reports while registered, and their seconds by event."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds: collections.Counter = collections.Counter()

    def __call__(self, event: str, *a, **_k) -> None:
        if event.startswith(("/jax/core/compile", "/jax/compilation_cache/")):
            self.count += 1
            self.seconds[event.rsplit("/", 1)[-1]] += a[0] if a else 0.0

    def listen(self) -> None:
        jax.monitoring.register_event_listener(self)
        jax.monitoring.register_event_duration_secs_listener(self)

    def stop(self) -> None:
        jax.monitoring.unregister_event_listener(self)
        jax.monitoring.unregister_event_duration_listener(self)


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def profile_options():
    """Device operations and the benchmark's own host spans only: no Python
    function events, no host-runtime detail, which slow the host."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    return opts


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    return np.random.SeedSequence(seed % 2**64).generate_state(n)


def entry_point(cfg: dict):
    sys.path.insert(0, str(spec.ROOT / "src"))
    module, name = cfg["entry"].split(":")
    return getattr(importlib.import_module(module), name)


def timed_program(cfg: dict, entry):
    """``entry`` under ``jax.jit``, every matrix product and conv in it
    traced at the configuration's ``matmul_precision``."""
    precision = cfg["matmul_precision"]

    def call(params, x):
        with jax.default_matmul_precision(precision):
            return entry(params, x)
    return jax.jit(call)


def program(bench: spec.Bench, key: tuple, make):
    """``jax.jit(make())``, built once per benchmark object and key."""
    if key not in bench.programs:
        bench.programs[key] = jax.jit(make())
    return bench.programs[key]


def make_pool(bench, adapter, cfg: dict, traffic: dict, key, seed: int):
    """The traffic's input batches: on the device in one program, or in
    host memory."""
    n, b = traffic["pool"], traffic["batch"]
    if traffic["inputs"] == "host":
        words = seed_words(seed, 4)[2:]
        xs = adapter.host_inputs(cfg, words, n * b)
        return [xs[i * b:(i + 1) * b] for i in range(n)]

    def make():
        return lambda k: tuple(adapter.init_inputs(cfg, kk, b)
                               for kk in jax.random.split(k, n))
    return list(program(bench, ("pool", cfg["name"], n, b), make)(key))


def compare(bench, adapter, cfg: dict, params, pool, w) -> dict:
    """The adapter's checks of the window's kept answers against its
    reference, run on the same inputs in blocks of ``REF_BLOCK``."""
    xs = np.concatenate([np.asarray(pool[slot]) for _, slot, _ in w.sample])
    ys = np.concatenate([np.asarray(y) for _, _, y in w.sample])
    ref = program(bench, ("reference", cfg["name"]),
                  lambda: partial(adapter.reference, cfg))
    rs = np.concatenate([np.asarray(ref(params, xs[i:i + REF_BLOCK]))
                         for i in range(0, len(xs), REF_BLOCK)])
    return adapter.checks(cfg, ys, rs)


class Run:
    """What a metric reader is given: the cell's configuration, adapter,
    traffic, peaks and ``flops_peak`` (at the configuration's precision);
    ``window``, the measured window (``--trace 1``: an untraced window of
    the traffic's ``trace_seconds``); ``traced``, the window that follows
    it under the profiler, and ``trace``, its reduced trace."""

    def __init__(self, cfg, adapter, traffic, peaks, flops_peak, window,
                 traced=None, trace=None) -> None:
        self.cfg = cfg
        self.adapter = adapter
        self.traffic = traffic
        self.peaks = peaks
        self.flops_peak = flops_peak
        self.window = window
        self.traced = traced
        self.trace = trace


def run_cell(bench: spec.Bench, workload: str, seed: int, seconds: float,
             trace: bool, platforms=("tpu",), entry=None) -> dict:
    """One run of one cell; returns the result object.  ``entry`` puts
    another function in the place of the configuration's entry point
    (the control and the broken paths of the tests)."""
    marks = [("imports", time.perf_counter())]
    w_spec = bench.workload(workload)
    cfg = bench.config(w_spec["config"])
    traffic = bench.traffic(w_spec["traffic"])
    gen = bench.generator(traffic)
    gen.validate(traffic)
    metrics = bench.per_layer(workload) if trace else bench.end_to_end(workload)
    readers = {m["name"]: bench.metric_reader(m["name"]) for m in metrics
               if m["name"] != "setup_s"}
    adapter = bench.adapter(cfg["model"])

    use_compile_cache()
    devices = jax.devices()
    marks.append(("tpu_init", time.perf_counter()))
    dev = devices[0]
    if dev.platform not in platforms or len(devices) < w_spec["chips"]:
        raise NoDevice(f"found {len(devices)} {dev.platform} device(s); "
                       f"{workload} needs {w_spec['chips']} of "
                       f"{'/'.join(platforms)}")
    peaks = bench.peaks(dev.device_kind) if trace else None
    flops_peak = bench.flops_peak(dev.device_kind, cfg) if trace else None

    fn = timed_program(cfg, entry or entry_point(cfg))
    setup_compiles = CompileCount()
    setup_compiles.listen()
    key = jax.random.wrap_key_data(np.asarray(seed_words(seed), np.uint32))
    jax.block_until_ready(key)
    marks.append(("first_transfer", time.perf_counter()))
    init = program(bench, ("params", cfg["name"]),
                   lambda: partial(adapter.init_params, cfg))
    k0 = jax.random.fold_in(key, 0)
    init = init.lower(k0).compile()
    marks.append(("params_compile", time.perf_counter()))
    params = init(k0)
    jax.block_until_ready(params)
    marks.append(("params_run", time.perf_counter()))
    pool = make_pool(bench, adapter, cfg, traffic, jax.random.fold_in(key, 1),
                     seed)
    marks.append(("inputs", time.perf_counter()))
    gen.run_window(fn, params, pool, traffic, 0.5, seed)   # warm-up
    marks.append(("compile_and_warm_up", time.perf_counter()))
    setup_s = marks[-1][1] - T_START
    setup_compiles.stop()
    print("setup_phases_s", " ".join(
        f"{name}={t - prev:.3f}" for (name, t), prev
        in zip(marks, [T_START] + [t for _, t in marks])), flush=True)
    print("setup_compile_events", " ".join(
        f"{name}={sec:.3f}" for name, sec in sorted(setup_compiles.seconds.items())),
        flush=True)

    compiles = CompileCount()
    compiles.listen()
    try:
        w = gen.run_window(fn, params, pool, traffic,
                           traffic["trace_seconds"] if trace else seconds, seed)
        if trace:
            traced, reduced = traced_window(gen, fn, params, pool, traffic,
                                            seed)
    finally:
        compiles.stop()
    print(f"compiles_in_window {compiles.count}", flush=True)

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    if trace:
        run = Run(cfg, adapter, traffic, peaks, flops_peak, w, traced, reduced)
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        # the profiler's own cost: time per query traced and untraced
        print("query_ms traced {!r} untraced {!r}".format(
            1e3 * traced.seconds / max(1, traced.attempted),
            1e3 * w.seconds / max(1, w.attempted)), flush=True)
    else:
        run = Run(cfg, adapter, traffic, peaks, flops_peak, w)
    values = {m["name"]: (setup_s if m["name"] == "setup_s"
                          else readers[m["name"]].read(run), m["unit"])
              for m in metrics}
    result = {"correct": False, "attempted": w.attempted,
              "failed": w.attempted - len(w.latencies),
              "metrics": {name: {"value": v, "unit": unit}
                          for name, (v, unit) in values.items()
                          if v is not None},
              "device": device}
    if trace:
        result["breakdown"] = reduced.breakdown()
    # the reference runs after the window and the memory reading
    checks = compare(bench, adapter, cfg, params, pool, w)
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    return result


def traced_window(gen, fn, params, pool, traffic: dict, seed: int):
    """A window of the traffic's ``trace_seconds`` under the profiler, with
    the benchmark's host spans; the trace is read and deleted."""
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        with jax.profiler.trace(tmp, profiler_options=profile_options()):
            w = gen.run_window(fn, params, pool, traffic,
                               traffic["trace_seconds"], seed,
                               span=jax.profiler.TraceAnnotation)
        path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        return w, devtrace.reduce(path)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(spec.Bench(), args.workload, args.seed,
                          args.seconds, bool(args.trace))
    except NoDevice as e:
        print(f"no device: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
