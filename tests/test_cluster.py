"""Cluster-parallel execution path (`repro.kernels.api.qdot_sharded` /
`qconv_sharded` + `repro.parallel.sharding` packed-artifact rules).

The conftest forces 8 host-platform devices, so these run the real
shard_map path on an 8-"core" cluster mesh on CPU (the CI parity job pins
the same XLA_FLAGS). Core claim under test: with packed weights sharded
over the output-feature axis and K unsharded, the sharded op is
**bit-exact** vs the single-device `eager_ref` oracle across the {8,4,2}²
bit grid — the psum-free epilogue argument of the paper's cluster.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing
from repro.core.quantize import QuantizedLinearParams
from repro.kernels import api
from repro.parallel.ctx import make_mesh
from repro.parallel.sharding import (packed_conv_specs, packed_linear_specs,
                                     shard_packed_conv, shard_packed_linear)

BITS = (8, 4, 2)
NDEV = len(jax.devices())

needs_cluster = pytest.mark.skipif(
    NDEV < 2, reason="needs >=2 devices (XLA_FLAGS="
                     "--xla_force_host_platform_device_count=8)")


def _mesh(dp, tp):
    return make_mesh((dp, tp), ("data", "model"),
                     devices=jax.devices()[: dp * tp])


def _mesh_shapes():
    """(dp, tp) variants that fit the available devices: pure DP, pure
    TP, and mixed."""
    shapes = [(NDEV, 1), (1, NDEV)]
    if NDEV >= 4:
        shapes.append((2, NDEV // 2))
    return shapes


def _mixed_mesh():
    """One DP x TP mesh exercising both axes at once (the {8,4,2}² grid
    runs here; the full layout sweep runs at fixed bits). Capped at 2x2 —
    per-call compile cost on host devices grows with device count, and 4
    devices already prove the DP x TP composition; the 8-device layouts
    are covered by the *_all_mesh_layouts tests."""
    return _mesh(2, 2) if NDEV >= 4 else _mesh(1, NDEV)


def _mk_qdot_params(rng, a_bits, w_bits, K=256, N=128):
    lo, hi = packing.int_range(w_bits, True)
    w = rng.integers(lo, hi + 1, size=(K, N)).astype(np.int8)
    wp = packing.pack(jnp.asarray(w), w_bits, axis=0)
    return QuantizedLinearParams(
        w_packed=wp, w_bits=w_bits, a_bits=a_bits, a_signed=False,
        kappa=jnp.asarray(rng.integers(-64, 64, (N,)).astype(np.int32)),
        lam=jnp.asarray(rng.integers(-2**16, 2**16, (N,)).astype(np.int32)),
        m=jnp.asarray(rng.integers(0, 2**15, (N,)).astype(np.int32)),
        d=18, out_bits=8, k_logical=K)


def _mk_acts(rng, a_bits, M=16, K=256):
    lo, hi = packing.int_range(a_bits, False)
    return jnp.asarray(rng.integers(lo, hi + 1, (M, K)).astype(np.int8))


def _mk_conv(rng, a_bits, w_bits, H=8, W=8, cin=24, cout=32):
    from repro.core import calibrate_activation, calibrate_weight
    from repro.core.quantize import QuantSpec, quantize
    from repro.kernels.qconv import quantize_conv

    x = np.maximum(rng.normal(size=(2, H, W, cin)), 0).astype(np.float32)
    w = rng.normal(size=(3, 3, cin, cout)).astype(np.float32) * 0.08
    sw = calibrate_weight(jnp.asarray(w), w_bits)
    sx = calibrate_activation(x, a_bits, 100.0)
    sy = QuantSpec.activation(a_bits, 8.0)
    qp = quantize_conv(jnp.asarray(w), sw,
                       rng.normal(size=(cout,)).astype(np.float32) * .05 + .3,
                       np.zeros((cout,), np.float32), sx, sy, 1, 1)
    return qp, quantize(jnp.asarray(x), sx)


# ----------------------------------------------------- sharding rules ---

@needs_cluster
def test_packed_linear_specs_shard_n_only(rng):
    """The packed K axis must never be sharded; N + epilogue vectors
    shard together over the tp axis."""
    params = _mk_qdot_params(rng, 8, 4)
    mesh = _mesh(1, NDEV)
    specs = packed_linear_specs(params, mesh)
    assert tuple(specs["w_packed"]) == (None, "model")
    assert tuple(specs["kappa"]) == ("model",)
    assert tuple(specs["lam"]) == ("model",)
    assert tuple(specs["m"]) == ("model",)


@needs_cluster
def test_packed_specs_raise_on_ragged_n(rng):
    """N not divisible by tp is a mis-sized artifact, not a fallback."""
    params = _mk_qdot_params(rng, 8, 8, N=130)  # 130 % NDEV != 0 for 4/8
    mesh = _mesh(1, NDEV)
    if 130 % NDEV == 0:
        pytest.skip("N divides this device count")
    with pytest.raises(ValueError, match="not divisible"):
        packed_linear_specs(params, mesh)
    with pytest.raises(ValueError, match="not divisible"):
        api.qdot(params, _mk_acts(rng, 8), mesh=mesh)


def test_packed_specs_tp1_replicated(rng):
    """A tp=1 (or absent) axis yields fully-replicated specs."""
    params = _mk_qdot_params(rng, 8, 8)
    mesh = _mesh(max(NDEV, 1), 1)
    specs = packed_linear_specs(params, mesh)
    assert tuple(specs["w_packed"]) == (None, None)


# ------------------------------------------------------- qdot parity ---

@needs_cluster
@pytest.mark.parametrize("ab", BITS)
@pytest.mark.parametrize("wb", BITS)
def test_qdot_sharded_bit_exact(ab, wb, rng):
    """Sharded qdot == single-device eager_ref across the bit grid on a
    mixed DP x TP mesh."""
    params = _mk_qdot_params(rng, ab, wb)
    x = _mk_acts(rng, ab)
    want = np.asarray(api.qdot(params, x, backend="eager_ref"))
    got = np.asarray(api.qdot(params, x, mesh=_mixed_mesh()))
    assert np.array_equal(got, want), (ab, wb)


@needs_cluster
def test_qdot_sharded_all_mesh_layouts(rng):
    """Pure-DP, pure-TP, and mixed meshes all agree with the oracle
    (fixed bits; the bit grid runs on the mixed mesh above)."""
    params = _mk_qdot_params(rng, 4, 4)
    x = _mk_acts(rng, 4)
    want = np.asarray(api.qdot(params, x, backend="eager_ref"))
    for dp, tp in _mesh_shapes():
        got = np.asarray(api.qdot(params, x, mesh=_mesh(dp, tp)))
        assert np.array_equal(got, want), (dp, tp)


@needs_cluster
def test_qdot_sharded_backends_and_presharded(rng):
    """Explicit backends agree on the sharded path; pre-sharding the
    artifact with `shard_packed_linear` (the fig9/serving setup) changes
    placement, not values."""
    params = _mk_qdot_params(rng, 4, 4)
    x = _mk_acts(rng, 4)
    want = np.asarray(api.qdot(params, x, backend="eager_ref"))
    mesh = _mesh(1, NDEV)
    for backend in ("xla", "pallas_interpret"):
        got = np.asarray(api.qdot(params, x, mesh=mesh, backend=backend))
        assert np.array_equal(got, want), backend
    sharded = shard_packed_linear(params, mesh)
    got = np.asarray(api.qdot(sharded, x, mesh=mesh))
    assert np.array_equal(got, want)


@needs_cluster
def test_qdot_sharded_ragged_m_pads(rng):
    """Row counts that don't divide dp are padded and sliced back."""
    params = _mk_qdot_params(rng, 8, 4)
    for m in (1, 13):
        x = _mk_acts(rng, 8, M=m)
        want = np.asarray(api.qdot(params, x, backend="eager_ref"))
        got = np.asarray(api.qdot(params, x, mesh=_mesh(NDEV, 1)))
        assert got.shape == want.shape == (m, 128)
        assert np.array_equal(got, want), m


@needs_cluster
def test_qdot_sharded_lead_dims_and_scale(rng):
    """Leading dims restore; per-channel dequant scale shards with N."""
    params = _mk_qdot_params(rng, 4, 4)
    x3 = _mk_acts(rng, 4, M=12).reshape(3, 4, 256)
    mesh = _mesh(2, NDEV // 2) if NDEV >= 4 else _mesh(1, NDEV)
    got = np.asarray(api.qdot(params, x3, mesh=mesh))
    want = np.asarray(api.qdot(params, x3, backend="xla"))
    assert got.shape == (3, 4, 128)
    assert np.array_equal(got, want)
    scale = rng.uniform(0.5, 2.0, size=(128,)).astype(np.float32)
    got = np.asarray(api.qdot(params, x3, mesh=mesh, epilogue="dequant",
                              scale=jnp.asarray(scale)), np.float32)
    want = np.asarray(api.qdot(params, x3, backend="xla",
                               epilogue="dequant",
                               scale=jnp.asarray(scale)), np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-2)


@needs_cluster
def test_qdot_sharded_rejects_eager_ref(rng):
    params = _mk_qdot_params(rng, 8, 8)
    with pytest.raises(ValueError, match="eager_ref"):
        api.qdot(params, _mk_acts(rng, 8), mesh=_mesh(1, NDEV),
                 backend="eager_ref")


# ------------------------------------------------------ qconv parity ---

@needs_cluster
@pytest.mark.parametrize("ab", BITS)
@pytest.mark.parametrize("wb", BITS)
def test_qconv_sharded_bit_exact(ab, wb, rng):
    """Sharded qconv == single-device eager_ref across the bit grid on a
    mixed DP x TP mesh."""
    qp, xq = _mk_conv(rng, ab, wb)
    want = np.asarray(api.qconv(qp, xq, backend="eager_ref"))
    got = np.asarray(api.qconv(qp, xq, mesh=_mixed_mesh()))
    assert np.array_equal(got, want), (ab, wb)


@needs_cluster
def test_qconv_sharded_all_mesh_layouts(rng):
    """Every mesh layout agrees with the oracle at fixed bits."""
    qp, xq = _mk_conv(rng, 4, 4)
    want = np.asarray(api.qconv(qp, xq, backend="eager_ref"))
    for dp, tp in _mesh_shapes():
        got = np.asarray(api.qconv(qp, xq, mesh=_mesh(dp, tp)))
        assert np.array_equal(got, want), (dp, tp)


@needs_cluster
def test_qconv_sharded_presharded_and_ragged_batch(rng):
    """`shard_packed_conv` placement + a batch that doesn't divide dp."""
    qp, xq = _mk_conv(rng, 4, 4)   # batch of 2
    mesh = _mesh(1, NDEV)
    specs = packed_conv_specs(qp, mesh)
    assert tuple(specs["w_packed_fused"]) == (None, "model")
    sharded = shard_packed_conv(qp, mesh)
    want = np.asarray(api.qconv(qp, xq, backend="eager_ref"))
    got = np.asarray(api.qconv(sharded, xq, mesh=mesh))
    assert np.array_equal(got, want)
    if NDEV >= 4:  # 2 images over dp=4: padded waves sliced back
        got = np.asarray(api.qconv(qp, xq, mesh=_mesh(4, NDEV // 4)))
        assert got.shape == want.shape
        assert np.array_equal(got, want)
