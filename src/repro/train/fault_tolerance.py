"""Fault tolerance for 1000+-node runs: restartable loop, straggler watch,
elastic re-meshing.

This layer is hardware-independent logic (tested on CPU): the policies it
implements are the ones large fleets need —

* CHECKPOINT/RESTART: `run_restartable` wraps the train loop; any step that
  raises a (transient) error triggers restore-from-latest and replay.  The
  data pipeline is a pure function of step, so replayed batches are
  bit-identical.
* STRAGGLER MITIGATION: `StragglerWatch` keeps a robust running estimate of
  step time (median + MAD) and flags hosts/steps exceeding k·MAD; the
  launcher's hook can then trigger checkpoint-and-evict.  On TPU fleets the
  same signal feeds the reshard decision.
* ELASTIC SCALING: `elastic_remesh` re-carves the mesh for a new healthy
  device count and re-shards a state pytree onto it (device_put with the
  new NamedShardings — the checkpoint path works identically through
  restore_checkpoint(shardings=...)).
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any, Callable

import jax

from repro.checkpoint.ckpt import (CheckpointManager, latest_step,
                                   restore_checkpoint)
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StragglerWatch:
    """Flags steps (or, with per-host timings, hosts) that run k·MAD over
    the median step time."""

    k: float = 5.0
    window: int = 50
    _times: list[float] = dataclasses.field(default_factory=list)

    def observe(self, seconds: float) -> bool:
        """Record a step duration; True if it is a straggler event."""
        history = self._times[-self.window:]
        self._times.append(seconds)
        if len(history) < 10:
            return False
        med = statistics.median(history)
        mad = statistics.median([abs(t - med) for t in history]) or 1e-9
        return seconds > med + self.k * mad

    def observe_hosts(self, per_host_seconds: dict[str, float]
                      ) -> list[str]:
        """Multi-host variant: which hosts straggle this step."""
        vals = list(per_host_seconds.values())
        med = statistics.median(vals)
        mad = statistics.median([abs(v - med) for v in vals]) or 1e-9
        return [h for h, v in per_host_seconds.items()
                if v > med + self.k * mad]


# ---------------------------------------------------------------------------
# restartable training loop
# ---------------------------------------------------------------------------

class TransientError(RuntimeError):
    """A failure worth restarting from checkpoint (preemption, link flap)."""


@dataclasses.dataclass
class RunReport:
    steps_done: int
    restarts: int
    straggler_events: int
    final_metrics: dict | None


def run_restartable(*,
                    train_step: Callable[[Any, Any], tuple[Any, dict]],
                    init_state: Callable[[], Any],
                    batches: Callable[[int], Any],
                    ckpt_dir: str,
                    total_steps: int,
                    ckpt_every: int = 50,
                    max_restarts: int = 3,
                    state_shardings: Any | None = None,
                    fail_injector: Callable[[int], None] | None = None
                    ) -> RunReport:
    """Checkpointed training loop with restart-on-transient-failure.

    ``fail_injector(step)`` (tests) may raise TransientError to simulate a
    node loss; the loop restores from the latest checkpoint and replays.
    """
    mgr = CheckpointManager(ckpt_dir)
    watch = StragglerWatch()
    restarts = 0
    stragglers = 0
    metrics: dict | None = None

    def fresh_or_restored():
        state = init_state()
        start = 0
        last = latest_step(ckpt_dir)
        if last is not None:
            state, extra = restore_checkpoint(ckpt_dir, state,
                                              shardings=state_shardings)
            start = extra["step"] + 1
        return state, start

    state, step = fresh_or_restored()
    while step < total_steps:
        try:
            t0 = time.monotonic()
            if fail_injector is not None:
                fail_injector(step)
            state, metrics = train_step(state, batches(step))
            jax.block_until_ready(metrics["loss"])
            if watch.observe(time.monotonic() - t0):
                stragglers += 1
            if step % ckpt_every == 0 or step == total_steps - 1:
                mgr.save_async(step, state, extra={})
            step += 1
        except TransientError:
            restarts += 1
            if restarts > max_restarts:
                raise
            mgr.wait()
            state, step = fresh_or_restored()
    mgr.wait()
    return RunReport(steps_done=step, restarts=restarts,
                     straggler_events=stragglers, final_metrics=metrics)


# ---------------------------------------------------------------------------
# elastic re-meshing
# ---------------------------------------------------------------------------

def elastic_remesh(n_devices: int, *, model_parallel: int
                   ) -> jax.sharding.Mesh:
    """Best (data, model) mesh for a surviving device count: keep the model
    axis (weights layout) and shrink data parallelism."""
    if n_devices % model_parallel:
        # degrade model parallelism to the largest divisor that fits
        while model_parallel > 1 and n_devices % model_parallel:
            model_parallel //= 2
    data = n_devices // model_parallel
    return make_mesh((data, model_parallel), ("data", "model"))


def reshard_state(state: Any, spec_tree: Any,
                  mesh: jax.sharding.Mesh) -> Any:
    from jax.sharding import NamedSharding
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        state, spec_tree)
