"""ResNet18 through BOTH stacks: the JAX model (numerics) and the PIM PPA
framework (the paper's evaluation), plus the Pallas fused-conv kernel.

1. run the JAX ResNet18 monolithically and as the paper's fused groups —
   outputs must match exactly (fusion is an execution-order change);
2. execute the stem conv through the fused CONV_BN_RELU Pallas kernel and
   compare against the XLA path;
3. evaluate the same network on the PIM simulator and print the PPA table.

Run:  PYTHONPATH=src python examples/resnet_pim_ppa.py
"""

from __future__ import annotations

import jax
import numpy as np

from repro.experiment import default_experiment
from repro.kernels.fused_conv import fused_conv_kernel
from repro.models.resnet import forward, forward_fused_groups, init_resnet18


def main() -> None:
    key = jax.random.PRNGKey(0)
    params = init_resnet18(key, 1000)
    x = jax.random.normal(key, (2, 96, 96, 3))

    y_mono = forward(params, x)
    y_fused = forward_fused_groups(params, x)
    np.testing.assert_allclose(np.asarray(y_mono), np.asarray(y_fused),
                               atol=1e-4)
    print(f"fused-group execution == monolithic ✓ (logits {y_mono.shape})")

    # stem conv through the Pallas fused kernel, in interpret mode: this
    # example runs on the CPU (chip_smoke.py runs the compiled kernel)
    bn = params["bn1"]
    inv = jax.lax.rsqrt(bn["var"] + 1e-5)
    scale = (bn["scale"] * inv).astype(x.dtype)
    shift = (bn["bias"] - bn["mean"] * inv * bn["scale"]).astype(x.dtype)
    y_kernel = fused_conv_kernel(x, params["conv1"], scale, shift,
                                 stride=2, padding=3, relu=True,
                                 tile_h=4, cout_block=64, interpret=True)
    ref = jax.nn.relu(
        (jax.lax.conv_general_dilated(
            x, params["conv1"], (2, 2), [(3, 3), (3, 3)],
            dimension_numbers=("NHWC", "HWIO", "NHWC")) - bn["mean"])
        * inv * bn["scale"] + bn["bias"])
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(ref),
                               atol=1e-3)
    print("Pallas fused CONV_BN_RELU == XLA reference ✓")

    print("\nPIM PPA (normalized to AiM-like G2K_L0):")
    exp = default_experiment()
    for r in exp.sweep(workloads="ResNet18_Full"):  # registry default points
        n = exp.normalized(r)
        print(f"  {r.system:10s} {r.config:9s} cycles={n['cycles']:.3f} "
              f"energy={n['energy']:.3f} area={n['area']:.3f}")


if __name__ == "__main__":
    main()
