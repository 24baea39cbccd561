"""Activation-sharding context: logical constraints inside model code.

**Paper analogy (XpulpNN §V):** an active mesh is the paper's parallel
cluster — one JAX device per cluster core. `make_mesh` is the repo-wide
way to build that cluster, and `jax.set_mesh` enters it; everything
layered above (`repro.kernels.api.qdot_sharded`, the serve engine's wave
sharding, the GSPMD constraints below) assumes it. Packed sub-byte arrays
inside the context obey the invariants in `repro.parallel.sharding`:
sharded only on the output-feature axis, never on the packed reduction
axis (a shard boundary inside a CHUNK group would split int8 containers
across cores).

Model code calls `constrain(x, axes)` (or `constrain_first(x, options)`)
on major intermediates; when a mesh context is active (set by the step
builders during tracing) this lowers to with_sharding_constraint with the
rules-resolved PartitionSpec; otherwise it is a no-op, so the same model
code runs unsharded in unit tests.

Without these constraints GSPMD replicates attention/MLP activations over
the `model` axis (observed: 78 GiB/device temp for a 1B model at train_4k —
the scores tensor was materialized with ALL heads per device).
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.parallel.sharding import DEFAULT_RULES, shard_spec_for

_ACTIVE = contextvars.ContextVar("repro_mesh_ctx", default=None)


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with Auto axes.

    The sharding constraints below and the `shard_map` bodies of the
    cluster path are GSPMD-style: they need Auto mesh axes, and
    `jax.make_mesh` builds Explicit ones unless told otherwise. Every
    mesh of the repo (launchers, benchmarks, tests) is built here.
    """
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


@contextlib.contextmanager
def activation_sharding(mesh, rules=DEFAULT_RULES):
    tok = _ACTIVE.set((mesh, rules))
    try:
        yield
    finally:
        _ACTIVE.reset(tok)


def active_mesh():
    ctx = _ACTIVE.get()
    return ctx[0] if ctx else None


def constrain(x, axes):
    """Constrain x's sharding by logical axes (None entries replicated).
    Non-divisible axes are dropped per shard_spec_for. No-op without an
    active mesh context."""
    ctx = _ACTIVE.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = shard_spec_for(x.shape, axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_first(x, options):
    """Apply the first option whose mesh-mapped axes all divide — e.g.
    shard attention over heads when possible, else over sequence (context
    parallelism fallback for few-head GQA archs)."""
    ctx = _ACTIVE.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    for axes in options:
        spec = shard_spec_for(x.shape, axes, mesh, rules)
        want = rules.spec(axes, mesh)
        if tuple(spec) == tuple(want):   # nothing was dropped
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
    return constrain(x, options[-1])
