"""Elastic scaling integration: checkpoint on one mesh, restore resharded
onto a different mesh, training continues bit-consistently.

Runs in a subprocess (multi-device via XLA_FLAGS before first jax import).
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


def test_checkpoint_reshard_across_meshes(tmp_path):
    out = _run(f"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import build_model
from repro.core.policies import get_policy
from repro.checkpoint.ckpt import save_checkpoint, restore_checkpoint
from repro.train.trainer import named, state_spec
from repro.launch.mesh import make_mesh
from repro.train.fault_tolerance import elastic_remesh

cfg = get_config('qwen3-32b', smoke=True)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))

# shard on a 2x4 mesh, checkpoint
mesh_a = make_mesh((2, 4), ('data', 'model'))
pol_a = get_policy('layerwise_tp', mesh_a, cfg)
spec_a = pol_a.param_spec(params)
sharded_a = pol_a.shard(params, spec_a)
save_checkpoint('{tmp_path}', 1, sharded_a)

# "lose" half the fleet: re-mesh to 4 devices and restore RESHARDED
mesh_b = elastic_remesh(4, model_parallel=4)
pol_b = get_policy('layerwise_tp', mesh_b, cfg)
spec_b = pol_b.param_spec(params)
from jax.sharding import NamedSharding
shardings_b = jax.tree.map(lambda s: NamedSharding(mesh_b, s), spec_b)
restored, extra = restore_checkpoint('{tmp_path}', params,
                                     shardings=shardings_b)
assert extra['step'] == 1

# values identical; shardings live on the new mesh
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
leaf = jax.tree.leaves(restored)[0]
assert leaf.sharding.mesh.devices.size == 4

# training still steps on the new mesh
from repro.train.trainer import TrainStepConfig, init_train_state, make_train_step
from repro.data.pipeline import batch_for_step
ts = TrainStepConfig(schedule_warmup=1)
state = init_train_state(model, restored, ts)
with jax.set_mesh(mesh_b):
    state, metrics = jax.jit(make_train_step(model, ts))(
        state, batch_for_step(cfg, 0, 4, 16))
assert np.isfinite(float(metrics['loss']))
print('elastic ok')
""")
    assert "elastic ok" in out
