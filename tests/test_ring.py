"""shard_map ring collectives vs dense references."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.launch.mesh import make_host_mesh
from repro.parallel.ring import collective_matmul, ring_decode_attention


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh(model=max(len(jax.devices()) // 1, 1))


def test_collective_matmul_matches_dense(mesh):
    n = mesh.shape["model"]
    rng = np.random.default_rng(0)
    M, K, N = 16, 32 * n, 24 * n
    x = rng.normal(size=(M, K)).astype(np.float32)
    w = rng.normal(size=(K, N)).astype(np.float32)
    with jax.set_mesh(mesh):
        y = collective_matmul(jnp.asarray(x), jnp.asarray(w), mesh)
    np.testing.assert_allclose(np.asarray(y), x @ w, rtol=2e-4, atol=1e-4)


def test_ring_decode_attention_matches_dense(mesh):
    n = mesh.shape["model"]
    rng = np.random.default_rng(1)
    B, T, H, Dh = 2, 16 * n, 4, 32
    q = rng.normal(size=(B, H, Dh)).astype(np.float32)
    k = rng.normal(size=(B, T, H, Dh)).astype(np.float32)
    v = rng.normal(size=(B, T, H, Dh)).astype(np.float32)
    # causal-style validity: first t_valid positions per row
    t_valid = rng.integers(1, T, size=(B,))
    mask = np.arange(T)[None, :] < t_valid[:, None]
    with jax.set_mesh(mesh):
        out = ring_decode_attention(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), jnp.asarray(mask), mesh)
    # dense reference
    s = np.einsum("bhd,bthd->bht", q, k) / np.sqrt(Dh)
    s = np.where(mask[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = np.where(mask[:, None, :], p, 0)
    ref = np.einsum("bht,bthd->bhd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_ring_attention_empty_shard_safe(mesh):
    """A shard whose mask is entirely False must contribute zeros, not
    NaNs (happens whenever index < shard offset in long-context decode)."""
    n = mesh.shape["model"]
    B, T, H, Dh = 1, 8 * n, 2, 16
    rng = np.random.default_rng(2)
    q = rng.normal(size=(B, H, Dh)).astype(np.float32)
    k = rng.normal(size=(B, T, H, Dh)).astype(np.float32)
    v = rng.normal(size=(B, T, H, Dh)).astype(np.float32)
    mask = np.zeros((B, T), bool)
    mask[:, :3] = True  # only the first shard sees valid keys
    with jax.set_mesh(mesh):
        out = ring_decode_attention(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), jnp.asarray(mask), mesh)
    assert np.isfinite(np.asarray(out)).all()
