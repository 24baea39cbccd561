"""GPipe over the pod axis == serial layer application (bitwise-close)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.parallel.pipeline import pipeline_apply, stage_stack
from repro.parallel.ctx import make_mesh


@pytest.fixture(scope="module")
def pod_mesh():
    devs = jax.devices()
    if len(devs) % 2:
        return make_mesh((1,), ("pod",))
    return make_mesh((min(2, len(devs)),), ("pod",),
                     devices=devs[: min(2, len(devs))]) \
        if len(devs) >= 2 else make_mesh((1,), ("pod",))


def test_pipeline_matches_serial(pod_mesh):
    n_stages = pod_mesh.shape["pod"]
    rng = np.random.default_rng(0)
    L = 4 * n_stages          # layers, split into stages
    d = 16
    w = rng.normal(size=(L, d, d)).astype(np.float32) * 0.3

    def layer(wi, h):
        return jnp.tanh(h @ wi)

    def serial(h):
        for i in range(L):
            h = layer(jnp.asarray(w[i]), h)
        return h

    def stage_fn(sp, h):
        def body(h, wi):
            return layer(wi, h), None
        h, _ = jax.lax.scan(body, h, sp["w"])
        return h

    n_micro, mb = 4, 3
    x = rng.normal(size=(n_micro, mb, d)).astype(np.float32)
    staged = stage_stack({"w": jnp.asarray(w)}, n_stages)
    with jax.set_mesh(pod_mesh):
        out = pipeline_apply(stage_fn, staged, jnp.asarray(x), pod_mesh)
    ref = np.stack([np.asarray(serial(jnp.asarray(x[i])))
                    for i in range(n_micro)])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)
