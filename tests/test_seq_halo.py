"""Windowed-halo attention == monolithic sliding-window attention.

Runs in a subprocess (needs >1 host device before first jax import).
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(py: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", py], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_windowed_halo_matches_reference():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.seq_halo import windowed_attention_halo
from repro.kernels.ref import attention_ref
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('model',))
key = jax.random.PRNGKey(0)
B, S, H, KV, D = 2, 128, 4, 2, 16
q = jax.random.normal(key, (B, S, H, D))
k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, D))
v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, D))
for window in (8, 16, 48):     # halo steps 1, 1, 3 at S_shard=16
    out = windowed_attention_halo(q, k, v, window=window, mesh=mesh)
    ref = attention_ref(
        q.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        k.transpose(0, 2, 1, 3).reshape(B * KV, S, D),
        v.transpose(0, 2, 1, 3).reshape(B * KV, S, D),
        causal=True, window=window)
    ref = ref.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
print('seq halo ok')
""")
    assert "seq halo ok" in out


def test_halo_bytes_model():
    from repro.core.seq_halo import halo_vs_gather_bytes
    # gemma2 @ prefill_32k, 16-way: S_shard=2048, W=4096 → 2 halo steps
    r = halo_vs_gather_bytes(32768, 4, 256, window=4096, n_shards=16)
    assert r["ratio"] == 15 / 2
    assert r["halo"] < r["all_gather"] / 7
    # degenerate: window spans everything → halo == gather
    r2 = halo_vs_gather_bytes(32768, 4, 256, window=32768, n_shards=16)
    assert r2["ratio"] == 1.0
