"""Production mesh builders. Importing this module never touches jax device
state — meshes are built inside functions only.

Single pod: (data=16, model=16) = 256 chips (v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the pod axis carries DP
(or pipeline stages for the 1T-class archs, see parallel/pipeline.py).
"""
from __future__ import annotations

import jax

from repro.parallel.ctx import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"))
