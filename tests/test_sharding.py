"""Sharding rules, divisibility fallback, attention strategy, and a real
jit'd train step on the host mesh with activation constraints active."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.parallel.sharding import DEFAULT_RULES, shard_spec_for


def test_rules_resolution():
    mesh = make_host_mesh()
    spec = DEFAULT_RULES.spec(("batch", None, "mlp"), mesh)
    assert spec[0] in ("data", ("data",)) or spec[0] is None or \
        isinstance(spec[0], tuple)


def test_divisibility_fallback():
    mesh = make_host_mesh()
    # dim 3 not divisible by any axis size > 1 -> replicated
    spec = shard_spec_for((3, 8), ("batch", "mlp"), mesh)
    n = mesh.shape.get("data", 1)
    if n > 1:
        assert spec[0] is None


def test_dedup_same_mesh_axis():
    """experts and expert_mlp both map to model: second occurrence must be
    dropped (PartitionSpec can't reuse a mesh axis)."""
    mesh = make_host_mesh()
    spec = DEFAULT_RULES.spec(("experts", "embed", "expert_mlp"), mesh)
    flat = []
    for e in spec:
        if isinstance(e, tuple):
            flat.extend(e)
        elif e is not None:
            flat.append(e)
    assert len(flat) == len(set(flat))


def test_attn_strategy():
    from repro.nn.attention import attn_strategy
    from repro.parallel.ctx import activation_sharding
    mesh = make_host_mesh()  # production mesh needs 256 devices
    with activation_sharding(mesh):
        m = mesh.shape.get("model", 1)
        assert attn_strategy(m, 1, 128, 128) == "tp"
        if m > 1:
            assert attn_strategy(m + 1, 1, m * 4, m * 4) == "cp"
    assert attn_strategy(1, 1, 4, 4) == "none"  # no active mesh


def test_host_mesh_train_step_with_constraints():
    from repro.configs.base import ShapeConfig
    from repro.configs.olmo_1b import smoke_config
    from repro.models.api import build
    from repro.train.step import TrainStepConfig, make_train_fns

    cfg = smoke_config()
    model = build(cfg)
    mesh = make_host_mesh()
    init_fn, step, shards = make_train_fns(
        model, mesh, ShapeConfig("t", 16, 2, "train"), TrainStepConfig())
    state = init_fn(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32)}
    with jax.set_mesh(mesh):
        state, m = jax.jit(step)(state, batch)
    assert np.isfinite(float(m["loss"]))
