import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# (docstring below; the two lines above MUST precede any other import —
# jax locks the device count at first init)

DOC = """Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: 512 host
placeholder devices stand in for 2 pods × 256 chips.  For every runnable
cell this script

    1. builds the model + policy and ShapeDtypeStruct inputs (no alloc),
    2. ``jax.jit(step).lower(...)`` under the production mesh,
    3. ``.compile()`` — sharding mismatches / unsupported collectives fail
       here,
    4. records ``memory_analysis()`` (fits-per-device proof),
       ``cost_analysis()`` (FLOPs/bytes) and the collective-transfer bytes
       parsed from the lowered HLO — the §Roofline inputs.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun [--cells a@s,b@s]
        [--mesh single|multi|both] [--policy fused_seq|layerwise_tp]
        [--out results.json]
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.policies import get_policy  # noqa: E402
from repro.data.pipeline import make_batch_specs  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.cells import Cell, all_cells, microbatch_for  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim.adamw import adamw_init  # noqa: E402
from repro.train.trainer import (TrainStepConfig, make_serve_step,  # noqa: E402
                                 make_train_step, named, state_spec)


def _shape_only(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def lower_cell(cell: Cell, mesh, policy_name: str, *, remat: bool = True,
               hints: bool = False, loss_chunk: int = 0, micro: int = 0):
    """Returns (lowered, compiled, meta) for one cell on one mesh.

    ``hints`` enables the §Perf sharding-constraint injection
    (core.hints); ``loss_chunk`` enables chunked head+CE."""
    cfg = get_config(cell.arch)
    model = build_model(cfg)
    policy = get_policy(policy_name, mesh, cfg)
    key = jax.random.PRNGKey(0)
    params_shapes = jax.eval_shape(model.init, key)
    pspec = policy.param_spec(params_shapes)
    data_par = 1
    for a in mesh.axis_names:
        if a in ("pod", "data"):
            data_par *= mesh.shape[a]

    shape = cell.shape
    if shape.kind == "train":
        micro = micro or microbatch_for(cell.arch, shape, data_par)
        ts = TrainStepConfig(microbatch=micro, remat=remat,
                             loss_chunk=loss_chunk)
        step = make_train_step(model, ts)
        batch = make_batch_specs(cfg, shape.global_batch, shape.seq_len)
        sspec = state_spec(policy, params_shapes)
        state_shapes = {"params": params_shapes,
                        "opt": jax.eval_shape(adamw_init, params_shapes)}
        bspec = policy.batch_spec(batch)
        fn = jax.jit(step, in_shardings=(named(mesh, sspec),
                                         named(mesh, bspec)))
        args = (state_shapes, batch)
    elif shape.kind == "prefill":
        def prefill(params, batch):
            logits, _ = model.forward(params, batch, remat=False,
                                      return_hidden=True)
            return logits

        batch = make_batch_specs(cfg, shape.global_batch, shape.seq_len)
        bspec = policy.batch_spec(batch)
        fn = jax.jit(prefill, in_shardings=(named(mesh, pspec),
                                            named(mesh, bspec)))
        args = (params_shapes, batch)
    else:  # decode
        serve = make_serve_step(model)
        cache_shapes = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        cspec = policy.cache_spec(cache_shapes)
        dp = policy._dp()
        tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        idx = jax.ShapeDtypeStruct((), jnp.int32)
        from repro.core.policies import repair_spec
        tok_spec = repair_spec(P(dp, None), tok.shape, mesh)
        fn = jax.jit(serve, in_shardings=(
            named(mesh, pspec), named(mesh, cspec),
            NamedSharding(mesh, tok_spec), NamedSharding(mesh, P())))
        args = (params_shapes, cache_shapes, tok, idx)

    import contextlib
    from repro.core import hints as hint_mod
    hint_ctx = contextlib.nullcontext()
    if hints:
        table = hint_mod.tp_hints(policy._dp()) \
            if policy_name == "layerwise_tp" \
            else hint_mod.fused_seq_hints(policy._dp())
        hint_ctx = hint_mod.sharding_hints(table)
    with jax.set_mesh(mesh), hint_ctx:
        t0 = time.monotonic()
        lowered = fn.lower(*args)
        t1 = time.monotonic()
        compiled = lowered.compile()
        t2 = time.monotonic()
    meta = {"lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2)}
    return lowered, compiled, meta


def analyze(cell: Cell, lowered, compiled, mesh, meta) -> dict:
    n_dev = mesh.devices.size
    rec = {"cell": cell.key, "mesh": "x".join(map(str, mesh.axis_sizes)),
           "status": "ok", **meta}
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            rec["bytes_per_device"] = {
                "argument": getattr(mem, "argument_size_in_bytes", None),
                "output": getattr(mem, "output_size_in_bytes", None),
                "temp": getattr(mem, "temp_size_in_bytes", None),
                "peak": getattr(mem, "peak_memory_in_bytes", None),
            }
    except Exception as e:  # noqa: BLE001 - CPU backend may not support
        rec["bytes_per_device"] = f"unavailable: {e}"
    try:
        cost = compiled.cost_analysis()
        rec["cost"] = {k: cost[k] for k in ("flops", "bytes accessed")
                       if k in cost}
    except Exception as e:  # noqa: BLE001
        rec["cost"] = f"unavailable: {e}"
    try:
        hc = hlo_analysis.analyze_hlo(compiled.as_text())
        rec["collectives"] = {
            **{k: int(v) for k, v in hc.collective_bytes.items()},
            "total": int(hc.collective_total),
            "count": hc.collective_count,
        }
        rec["hlo_flops_per_device"] = hc.flops        # trip-corrected
        rec["hlo_hbm_bytes_per_device"] = hc.hbm_bytes
        rec["while_trip_counts"] = hc.while_trip_counts
    except Exception as e:  # noqa: BLE001
        rec["collectives"] = f"unavailable: {e}"
    rec["num_devices"] = n_dev
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="",
                    help="comma-separated cell keys (default: all)")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--policy", default="fused_seq")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--hints", action="store_true",
                    help="enable §Perf sharding-constraint hints")
    ap.add_argument("--loss-chunk", type=int, default=0,
                    help="chunked head+CE sequence slice (0=off)")
    ap.add_argument("--micro", type=int, default=0,
                    help="override global microbatch size (0=auto)")
    args = ap.parse_args()

    wanted = set(filter(None, args.cells.split(",")))
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16",
                       make_production_mesh(multi_pod=True)))

    results = []
    for cell in all_cells():
        if wanted and cell.key not in wanted:
            continue
        if cell.skip_reason:
            results.append({"cell": cell.key, "status": "skip",
                            "reason": cell.skip_reason})
            print(f"SKIP {cell.key}: {cell.skip_reason}")
            continue
        for mesh_name, mesh in meshes:
            tag = f"{cell.key} [{mesh_name}] policy={args.policy}"
            try:
                lowered, compiled, meta = lower_cell(
                    cell, mesh, args.policy, remat=not args.no_remat,
                    hints=args.hints, loss_chunk=args.loss_chunk,
                    micro=args.micro)
                rec = analyze(cell, lowered, compiled, mesh, meta)
                rec["mesh_name"] = mesh_name
                rec["policy"] = args.policy
                results.append(rec)
                print(f"OK   {tag} lower={meta['lower_s']}s "
                      f"compile={meta['compile_s']}s")
            except Exception as e:  # noqa: BLE001 - report and continue
                results.append({"cell": cell.key, "mesh_name": mesh_name,
                                "policy": args.policy, "status": "fail",
                                "error": f"{type(e).__name__}: {e}"})
                print(f"FAIL {tag}: {type(e).__name__}: {e}")
                traceback.print_exc(limit=3)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    ok = sum(1 for r in results if r.get("status") == "ok")
    fail = sum(1 for r in results if r.get("status") == "fail")
    skip = sum(1 for r in results if r.get("status") == "skip")
    print(f"\n=== dry-run: {ok} ok, {fail} fail, {skip} skip "
          f"→ {args.out} ===")
    raise SystemExit(1 if fail else 0)


if __name__ == "__main__":
    main()
