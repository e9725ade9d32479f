"""Production training launcher.

Maps (architecture, policy, mesh) to the sharded restartable train loop:

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --smoke \
        --steps 50 --mesh 1x1 --policy fused_seq

On a real fleet the same entry point runs per host (jax.distributed
initialises from the cluster env); on this CPU container use ``--smoke``
configs and a 1×1 (or host-device) mesh.  Every run is checkpointed and
restartable; stragglers are logged via the watchdog.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config
from repro.core.policies import get_policy
from repro.data.pipeline import batch_for_step
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.models.api import param_count
from repro.optim.adamw import AdamWConfig
from repro.train.fault_tolerance import StragglerWatch, run_restartable
from repro.train.trainer import (TrainStepConfig, init_train_state,
                                 make_train_step, state_spec)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1",
                    help="DxM data×model mesh, e.g. 16x16")
    ap.add_argument("--policy", default="fused_seq",
                    choices=["fused_seq", "layerwise_tp",
                             "fused_seq_zero3"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    args = ap.parse_args()
    use_compile_cache()

    d, m = (int(v) for v in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    policy = get_policy(args.policy, mesh, cfg)

    ts = TrainStepConfig(opt=AdamWConfig(lr=args.lr),
                         microbatch=args.microbatch, remat=args.remat,
                         compress_grads=args.compress_grads,
                         schedule_total_steps=args.steps,
                         schedule_warmup=max(2, args.steps // 20))
    step_fn = jax.jit(make_train_step(model, ts))
    watch = StragglerWatch()

    def init_state():
        params = model.init(jax.random.PRNGKey(0))
        print(f"{cfg.name}: {param_count(params) / 1e6:.1f}M params on "
              f"{mesh.devices.size} devices, policy={policy.name}")
        state = init_train_state(model, params, ts)
        pshapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            state["params"])
        sspec = state_spec(policy, pshapes)
        state["params"] = policy.shard(state["params"], sspec["params"])
        state["opt"]["m"] = policy.shard(state["opt"]["m"],
                                         sspec["opt"]["m"])
        state["opt"]["v"] = policy.shard(state["opt"]["v"],
                                         sspec["opt"]["v"])
        return state

    t0 = time.time()
    count = [0]

    def step_and_log(state, batch):
        with jax.set_mesh(mesh):
            state, metrics = step_fn(state, batch)
        count[0] += 1
        k = count[0]
        dt = time.time() - t0
        if watch.observe(dt / k):
            print(f"  [straggler-watch] slow step {k}")
        if k % 10 == 0 or k == 1:
            print(f"step {k:5d}  loss {float(metrics['loss']):.4f}  "
                  f"lr {float(metrics['lr']):.2e}  {dt / k:.2f}s/step")
        return state, metrics

    report = run_restartable(
        train_step=step_and_log,
        init_state=init_state,
        batches=lambda s: batch_for_step(cfg, s, args.global_batch,
                                         args.seq),
        ckpt_dir=args.ckpt_dir,
        total_steps=args.steps,
        ckpt_every=args.ckpt_every)
    print(f"finished {report.steps_done} steps "
          f"({report.restarts} restarts, "
          f"{report.straggler_events} straggler events); final loss "
          f"{float(report.final_metrics['loss']):.4f}")


if __name__ == "__main__":
    main()
