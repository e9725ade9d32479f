"""A closed loop of queries, driven by a traffic file's parameters.

A traffic file (``traffic/<name>.json``) names its generator
(``"generator": "closed_loop"`` for this one) and sets:

* ``batch``: images per query;
* ``in_flight``: queries outstanding at once (1 = the next query is sent
  when the last answer is back, as in MLPerf SingleStream; more keep a
  queue on the device, as an offline batch loop does, so that a pause of
  the host does not idle the device);
* ``inputs``: ``"device"`` (a pool of batches made on the device in
  set-up, the query passes one) or ``"host"`` (a pool of images in host
  memory, the query copies its batch to the device first);
* ``outputs``: ``"device"`` (the answer stays on the device; the query is
  done when it is ready) or ``"host"`` (the query fetches its answer);
* ``pool``: distinct input batches, cycled in order;
* ``check_queries``: answers drawn from the seed and kept for the
  comparison with the reference;
* ``trace_seconds``: the length of the window in a traced run.

A query's latency runs from before its input is sent to its answer being
ready (or on the host).  The window closes when the last query sent before
``seconds`` is done.  The loop sends the same sequence of queries for a
given seed.  What it recorded (``Window``) is what the end-to-end metric
readers (``metrics/<name>.py``) read.
"""

from __future__ import annotations

import collections
import contextlib
import random
import time

import numpy as np

KEYS = {"generator", "batch", "in_flight", "inputs", "outputs", "pool",
        "check_queries", "trace_seconds"}


def validate(traffic: dict) -> None:
    missing = KEYS - set(traffic)
    if missing:
        raise ValueError(f"traffic file lacks {sorted(missing)}")
    if traffic["inputs"] not in ("device", "host") \
            or traffic["outputs"] not in ("device", "host"):
        raise ValueError("inputs and outputs are 'device' or 'host'")
    if min(traffic["batch"], traffic["in_flight"], traffic["pool"],
           traffic["check_queries"]) < 1:
        raise ValueError("batch, in_flight, pool and check_queries are >= 1")


class Window:
    """What one measured window did."""

    def __init__(self) -> None:
        self.attempted = 0
        self.images = 0
        self.latencies: list[float] = []
        self.seconds = 0.0
        # (query index, pool index, answer) of the answers kept for the check
        self.sample: list[tuple[int, int, object]] = []


def run_window(fn, params, pool, traffic: dict, seconds: float, seed: int,
               span=None) -> Window:
    """Drive ``fn(params, batch)`` for ``seconds`` in a closed loop.

    ``pool`` holds device arrays (``inputs: device``) or host arrays
    (``inputs: host``), each one query's batch.  ``span(name)`` gives a
    context manager around each host step (a profiler annotation in a
    traced run)."""
    import jax

    span = span or (lambda _name: contextlib.nullcontext())
    host_in = traffic["inputs"] == "host"
    host_out = traffic["outputs"] == "host"
    depth = traffic["in_flight"]
    keep = traffic["check_queries"]
    rng = random.Random(seed)
    w = Window()
    pending: collections.deque = collections.deque()

    def finish() -> None:
        i, slot, t_sent, y = pending.popleft()
        if host_out:
            with span("query.fetch"):
                y = np.asarray(y)
        else:
            with span("query.wait"):
                y.block_until_ready()
        w.latencies.append(time.perf_counter() - t_sent)
        w.images += traffic["batch"]
        # reservoir sample of the answers, drawn from the seed
        if len(w.sample) < keep:
            w.sample.append((i, slot, y))
        else:
            j = rng.randrange(i + 1)
            if j < keep:
                w.sample[j] = (i, slot, y)

    t0 = time.perf_counter()
    deadline = t0 + seconds
    i = 0
    while time.perf_counter() < deadline:
        slot = i % len(pool)
        t_sent = time.perf_counter()
        x = pool[slot]
        if host_in:
            with span("query.put"):
                x = jax.device_put(x)
        with span("query.call"):
            y = fn(params, x)
        pending.append((i, slot, t_sent, y))
        i += 1
        if len(pending) >= depth:
            finish()
    while pending:
        finish()
    w.seconds = time.perf_counter() - t0
    w.attempted = i
    return w
