"""Elastic fault tolerance: a checkpoint saved under one device count
restores under a DIFFERENT device count (node failure / scale change) —
exercised with real separate processes and XLA host-device overrides."""
import pathlib
import subprocess
import sys
import tempfile

import pytest

pytestmark = pytest.mark.slow  # two real training subprocesses

SAVE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, jax, jax.numpy as jnp
sys.path.insert(0, "src")
from repro.models.api import build
from repro.configs.olmo_1b import smoke_config
from repro.launch.mesh import make_host_mesh
from repro.train.step import make_train_fns, TrainStepConfig
from repro.configs.base import ShapeConfig
from repro.ckpt.checkpoint import save
cfg = smoke_config(); model = build(cfg)
mesh = make_host_mesh(model=2)   # 2x2 mesh
init_fn, step, shards = make_train_fns(model, mesh, ShapeConfig("t",16,4,"train"), TrainStepConfig())
state = init_fn(jax.random.PRNGKey(0))
batch = {"tokens": jnp.ones((4,16), jnp.int32), "labels": jnp.ones((4,16), jnp.int32)}
with jax.set_mesh(mesh):
    state, m = jax.jit(step)(state, batch)
save(sys.argv[1], 1, state)
print("SAVED", float(m["loss"]))
"""

RESTORE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, jax, jax.numpy as jnp
sys.path.insert(0, "src")
from repro.models.api import build
from repro.configs.olmo_1b import smoke_config
from repro.launch.mesh import make_host_mesh
from repro.train.step import make_train_fns, TrainStepConfig
from repro.configs.base import ShapeConfig
from repro.ckpt.checkpoint import restore
cfg = smoke_config(); model = build(cfg)
mesh = make_host_mesh(model=4)   # DIFFERENT mesh: 2x4
init_fn, step, shards = make_train_fns(model, mesh, ShapeConfig("t",16,4,"train"), TrainStepConfig())
state, s0 = restore(sys.argv[1], shardings=None)
batch = {"tokens": jnp.ones((4,16), jnp.int32), "labels": jnp.ones((4,16), jnp.int32)}
with jax.set_mesh(mesh):
    state, m = jax.jit(step)(state, batch)
print("RESTORED", s0, float(m["loss"]))
"""


def test_cross_device_count_restore():
    tmp = tempfile.mkdtemp()
    root = pathlib.Path(__file__).resolve().parents[1]
    r1 = subprocess.run([sys.executable, "-c", SAVE, tmp], cwd=root,
                        capture_output=True, text=True, timeout=300)
    assert "SAVED" in r1.stdout, r1.stderr[-2000:]
    r2 = subprocess.run([sys.executable, "-c", RESTORE, tmp], cwd=root,
                        capture_output=True, text=True, timeout=300)
    assert "RESTORED 1" in r2.stdout, r2.stderr[-2000:]
