#!/usr/bin/env python3
"""Bring-up check of the ResNet18 path on one TPU chip, or of the halo
path on four.

    python chip_smoke.py [--seed 0]     # one chip
    python chip_smoke.py --chips 4      # four chips

One chip: ResNet18 at full width (a batch of 128 224×224×3 float32
images, 1000 classes) through ``forward``, against the same program at
``"highest"`` matmul precision, then the compiled ``fused_conv`` Pallas
kernel at every conv geometry of ResNet18 (``core.graph.conv_geometries``),
each against ``kernels.ref.fused_conv_ref``.  Four chips: the
row-sharded fused-group halo exchange (``core.halo``) on ResNet18 stage-1
maps against the same group on one device, and nothing else.

Exits non-zero without a JSON line when JAX finds no TPU or any check
fails.  On success the last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
It times nothing: the chip benchmark (``benchmarks/chip/``) does.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.graph import ConvGeometry, conv_geometries  # noqa: E402
from repro.core.halo import run_fused_group, run_fused_group_exact  # noqa: E402
from repro.kernels.fused_conv import fused_conv_kernel  # noqa: E402
from repro.kernels.ref import fused_conv_ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models.resnet import forward, init_resnet18, stage  # noqa: E402

# Tolerances on max|out - ref| / max|ref|.
TOL_VS_HIGHEST = 5e-2       # default TPU matmul precision vs "highest"
TOL_KERNEL = 1e-4           # kernel dots are Precision.HIGHEST (f32)
TOL_HALO = 1e-4             # sharded vs one device, both "highest"

IMAGE = 224
BATCH = 128
CLASSES = 1000


class Checks:
    """Named pass/fail results; a phase that raises is one failed check."""

    def __init__(self) -> None:
        self.failed: list[str] = []

    def check(self, name: str, ok: bool, detail: str) -> None:
        print(f"[{'PASS' if ok else 'FAIL'}] {name}: {detail}", flush=True)
        if not ok:
            self.failed.append(name)

    def run(self, name: str, fn, *args) -> None:
        try:
            fn(self, *args)
        except Exception:
            traceback.print_exc()
            self.check(name, False, "raised (traceback on stderr)")


def rel_err(out, ref) -> float:
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.abs(out - ref).max() / np.abs(ref).max())


def compile_and_run(fn, *args):
    """AOT-compile ``fn`` for ``args`` and return its output on them."""
    return jax.block_until_ready(jax.jit(fn).lower(*args).compile()(*args))


def resnet_phase(ck: Checks, key, batch: int) -> None:
    params = init_resnet18(key, CLASSES)
    x = jax.random.normal(jax.random.fold_in(key, 1),
                          (batch, IMAGE, IMAGE, 3), jnp.float32)
    y = compile_and_run(forward, params, x)
    with jax.default_matmul_precision("highest"):
        ref = compile_and_run(forward, params, x)

    shape = (batch, CLASSES)
    ck.check("resnet18 forward logits finite",
             y.shape == shape and bool(jnp.isfinite(y).all()),
             f"shape {y.shape}, expected {shape}")
    e = rel_err(y, ref)
    ck.check("resnet18 forward == forward @highest",
             e <= TOL_VS_HIGHEST, f"rel err {e:.3e} <= {TOL_VS_HIGHEST:g}")


def fused_conv_geometry(ck: Checks, key, batch: int,
                        g: ConvGeometry) -> None:
    ks = jax.random.split(key, 5)
    oh = (g.hw + 2 * g.padding - g.k) // g.stride + 1
    x = jax.random.normal(ks[0], (batch, g.hw, g.hw, g.cin))
    w = jax.random.normal(ks[1], (g.k, g.k, g.cin, g.cout)) \
        * (2.0 / (g.k * g.k * g.cin)) ** 0.5
    scale = jax.random.normal(ks[2], (g.cout,)) * 0.1 + 1.0
    shift = jax.random.normal(ks[3], (g.cout,)) * 0.1
    args = [x, w, scale, shift]
    if g.residual:
        args.append(jax.random.normal(ks[4], (batch, oh, oh, g.cout)))
    conv = dict(stride=g.stride, padding=g.padding, relu=g.relu)

    def kern(x, w, scale, shift, residual=None):
        return fused_conv_kernel(x, w, scale, shift, residual=residual,
                                 **conv)

    def ref(x, w, scale, shift, residual=None):
        return fused_conv_ref(x, w, scale, shift, residual=residual, **conv)

    out = compile_and_run(kern, *args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref)(*args)
    e = rel_err(out, want)
    ck.check(f"fused_conv {g.name}",
             out.shape == want.shape and e <= TOL_KERNEL,
             f"shape {out.shape}, rel err {e:.3e} <= {TOL_KERNEL:g}")


def halo_phase(ck: Checks, key, batch: int, devices) -> None:
    """core.halo on a 4-way row-sharded ResNet18 stage-1 map (56×56×64)."""
    n = 4
    ck.check("four devices", len(devices) >= n, f"found {len(devices)}")
    mesh = make_mesh((n,), ("model",), devices=devices[:n])
    spec = NamedSharding(mesh, P(None, "model", None, None))
    params = init_resnet18(key, CLASSES)
    x = jax.random.normal(jax.random.fold_in(key, 1), (batch, 56, 56, 64))
    xs = jax.device_put(x, spec)
    shard_devs = {sh.device for sh in xs.addressable_shards}
    rows = sorted(sh.data.shape[1] for sh in xs.addressable_shards)
    ck.check("row shards on distinct devices", len(shard_devs) == n
             and rows == [56 // n] * n,
             f"{len(shard_devs)} devices, rows per shard {rows}")
    x1 = jax.device_put(x, devices[0])

    # stride-1 conv group at stage-1 width: the four 3x3 CONV_BN_RELU
    # layers of stage 1, receptive-field halo 4 rows
    layers = []
    for blk in ("s1b1", "s1b2"):
        for conv, bn in (("conv1", "bn1"), ("conv2", "bn2")):
            pb = params[blk]
            layers.append(
                lambda t, w=pb[conv], b=pb[bn]:
                jax.nn.relu(L.batchnorm(b, L.conv2d(w, t, 1, 1))))

    def group(t):
        for fn in layers:
            t = fn(t)
        return t

    def block(t):
        return stage(params, t, 0)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(group)(x1)
        got = compile_and_run(
            lambda t: run_fused_group_exact(layers, t, mesh, halo=4), xs)
        out_devs = {sh.device for sh in got.addressable_shards}
        e = rel_err(got, want)
        ck.check("run_fused_group_exact == one device (all rows)",
                 len(out_devs) == n and e <= TOL_HALO,
                 f"{len(out_devs)} devices, rel err {e:.3e} <= {TOL_HALO:g}")

        want = jax.jit(block)(x1)
        got = compile_and_run(
            lambda t: run_fused_group(block, t, mesh, halo=4, shrink=4), xs)
        shard = 56 // n
        inner = slice(shard, 56 - shard)       # rows of the interior shards
        e = rel_err(np.asarray(got)[:, inner], np.asarray(want)[:, inner])
        ck.check("run_fused_group(stage 1) == one device (interior shards)",
                 e <= TOL_HALO, f"rel err {e:.3e} <= {TOL_HALO:g}")
        print(f"  (info) boundary-shard rel err "
              f"{rel_err(got, want):.3e} (not promised exact)", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip halo path")
    args = ap.parse_args()

    cache = use_compile_cache()
    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    if d0.platform != "tpu":
        print("no TPU found: chip_smoke.py runs on the chip only",
              file=sys.stderr)
        return 2
    print(f"  (info) compile cache: {cache.directory}", flush=True)

    key = jax.random.PRNGKey(args.seed)
    ck = Checks()
    if args.chips == 4:
        ck.run("halo x4", halo_phase, key, BATCH, devices)
    else:
        ck.run("resnet18", resnet_phase, key, BATCH)
        for i, g in enumerate(conv_geometries(IMAGE)):
            ck.run(f"fused_conv {g.name}", fused_conv_geometry,
                   jax.random.fold_in(key, 100 + i), BATCH, g)
    print(f"  (info) compile cache: {cache.hits} hits, {cache.misses} misses",
          flush=True)
    if ck.failed:
        print(f"{len(ck.failed)} check(s) failed: {ck.failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
