"""The unified quantized-op backend API (`repro.kernels.api`).

* backend-parity suite: every registered backend per op agrees bit-exactly
  with `eager_ref` across the {8,4,2}^2 bit grid x epilogues;
* registry negative tests: unknown backends raise with the available list,
  supports=False backends are skipped in default resolution;
* resolution order: explicit arg -> REPRO_QBACKEND env -> capability
  default (xla on CPU — the real `pallas` backend asserts a TPU platform);
* deprecation shims: `use_kernel`/`interpret` kwargs, `QuantConfig`, plan
  schema v1 JSON (single warning, correct backend mapping, v2 re-save);
* `_int_matmul`-vs-`xla_int_gemm` dedupe regression (old inline
  implementation pinned here) for the W{8,4,2}A{8,4,2} grid;
* the autotune block cache: JSON round-trip, api lookup, env preload.
"""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (QuantSpec, calibrate_activation, calibrate_weight,
                        packing, quantize)
from repro.core.quantize import QuantizedLinearParams
from repro.kernels import api, tune
from repro.kernels.qconv import quantize_conv, qconv2d_apply
from repro.kernels.qmatmul import qlinear_apply
from repro.nn.layers import QuantConfig, dense_apply, pack_dense_weights
from repro.parallel.ctx import make_mesh

BITS = (8, 4, 2)


# ------------------------------------------------------------- fixtures ---

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


def _mk_conv(rng, a_bits, w_bits, H=8, W=8, cin=24, cout=40):
    x = np.maximum(rng.normal(size=(1, H, W, cin)), 0).astype(np.float32)
    w = rng.normal(size=(3, 3, cin, cout)).astype(np.float32) * 0.08
    sw = calibrate_weight(jnp.asarray(w), w_bits)
    sx = calibrate_activation(x, a_bits, 100.0)
    sy = QuantSpec.activation(a_bits, 8.0)
    qp = quantize_conv(jnp.asarray(w), sw,
                       rng.normal(size=(cout,)).astype(np.float32) * .05 + .3,
                       np.zeros((cout,), np.float32), sx, sy, 1, 1)
    return qp, quantize(jnp.asarray(x), sx)


def _supported(op, shape, a_bits, w_bits):
    plat = api.platform()
    return [n for n in api.backends(op)
            if api.get(op, n).supports(shape, a_bits, w_bits, plat)]


# --------------------------------------------------------- parity: qdot ---

@pytest.mark.parametrize("ab", BITS)
@pytest.mark.parametrize("wb", BITS)
def test_qdot_backend_parity_int(ab, wb, rng):
    """Every runnable backend == eager_ref, bit-exact, per bit pair."""
    params = _mk_qdot_params(rng, ab, wb)
    x = _mk_acts(rng, ab)
    want = np.asarray(api.qdot(params, x, backend="eager_ref"))
    names = _supported("qdot", (16, 256, 128), ab, wb)
    assert "xla" in names and "pallas_interpret" in names
    for name in names:
        got = np.asarray(api.qdot(params, x, backend=name))
        assert np.array_equal(got, want), (name, ab, wb)


@pytest.mark.parametrize("epilogue", ["int", "raw", "dequant"])
def test_qdot_backend_parity_epilogues(epilogue, rng):
    params = _mk_qdot_params(rng, 4, 4)
    x = _mk_acts(rng, 4)
    want = np.asarray(api.qdot(params, x, epilogue=epilogue, scale=0.25,
                               backend="eager_ref"), np.float32)
    for name in _supported("qdot", (16, 256, 128), 4, 4):
        got = np.asarray(api.qdot(params, x, epilogue=epilogue, scale=0.25,
                                  backend=name), np.float32)
        if epilogue == "dequant":
            np.testing.assert_allclose(got, want, rtol=1e-2)
        else:
            assert np.array_equal(got, want), (name, epilogue)


# -------------------------------------------------------- parity: qconv ---

@pytest.mark.parametrize("ab", BITS)
@pytest.mark.parametrize("wb", BITS)
def test_qconv_backend_parity(ab, wb, rng):
    qp, xq = _mk_conv(rng, ab, wb)
    want = np.asarray(api.qconv(qp, xq, backend="eager_ref"))
    shape = api._conv_shape(qp, xq)
    names = _supported("qconv", shape, ab, wb)
    assert {"xla", "pallas_interpret"} <= set(names)
    for name in names:
        got = np.asarray(api.qconv(qp, xq, backend=name))
        assert np.array_equal(got, want), (name, ab, wb)


# ------------------------------------------------------------- registry ---

def test_unknown_backend_raises_with_available_list():
    with pytest.raises(KeyError, match="available.*eager_ref"):
        api.get("qdot", "mosaic_gpu")
    params = _mk_qdot_params(np.random.default_rng(0), 8, 8)
    with pytest.raises(KeyError, match="no backend 'nope'"):
        api.qdot_packed(params, _mk_acts(np.random.default_rng(0), 8),
                        backend="nope")
    with pytest.raises(ValueError, match="unknown op"):
        api.register("qpool", "xla", supports=lambda *a: True, run=None)


def test_grouped_conv_rejected_cleanly(rng, monkeypatch):
    """Grouped/depthwise params: no registered qconv backend claims
    support, default resolution raises, and an explicit backend raises
    (instead of silently mis-shaping the ungrouped contraction)."""
    import dataclasses

    monkeypatch.delenv(api.ENV_VAR, raising=False)
    qp, xq = _mk_conv(rng, 8, 8)
    grouped = dataclasses.replace(qp, groups=2)
    shape = api._conv_shape(grouped, xq)
    assert api.conv_shape_groups(shape) == 2
    plat = api.platform()
    for name in api.backends("qconv"):
        assert not api.get("qconv", name).supports(shape, 8, 8, plat), name
    with pytest.raises(RuntimeError, match="no default backend supports"):
        api.qconv(grouped, xq)
    with pytest.raises(ValueError, match="grouped conv"):
        api.qconv(grouped, xq, backend="xla")
    with pytest.raises(ValueError, match="grouped conv"):
        api.qconv(grouped, xq, backend="pallas_interpret")
    # ungrouped params still resolve exactly as before (9- and 10-tuple
    # shape keys are both accepted by the supports helpers)
    assert api.conv_shape_groups(shape[:9]) == 1
    got = np.asarray(api.qconv(qp, xq, backend="xla"))
    want = np.asarray(api.qconv(qp, xq, backend="eager_ref"))
    assert np.array_equal(got, want)


def test_grouped_conv_rejected_under_mesh(rng):
    import dataclasses
    import jax

    qp, xq = _mk_conv(rng, 8, 8)
    grouped = dataclasses.replace(qp, groups=2)
    mesh = make_mesh((2, 1), ("data", "model"),
                     devices=jax.devices()[:2])
    with pytest.raises((RuntimeError, ValueError),
                       match="grouped conv|no default backend supports"):
        api.qconv(grouped, xq, mesh=mesh, backend="xla")


def test_default_resolution_skips_unsupported(monkeypatch):
    """supports=False backends are skipped; the capability order falls
    through to the first supporting backend."""
    monkeypatch.delenv(api.ENV_VAR, raising=False)
    api.register("qdot", "_test_never", supports=lambda *a: False, run=None)
    try:
        monkeypatch.setattr(api, "DEFAULT_ORDER", ("_test_never", "xla"))
        spec = api.resolve("qdot", (16, 256, 128), 8, 8)
        assert spec.name == "xla"
    finally:
        api._REGISTRY.pop(("qdot", "_test_never"))


def test_default_resolution_on_cpu_is_xla(monkeypatch):
    if api.platform() == "tpu":
        pytest.skip("CPU-only assertion")
    monkeypatch.delenv(api.ENV_VAR, raising=False)
    # pallas is first in capability order but requires TPU
    assert api.DEFAULT_ORDER[0] == "pallas"
    assert api.resolve("qdot", (16, 256, 128), 8, 8).name == "xla"
    assert api.default_backend("qconv") == "xla"


def test_pallas_backend_asserts_real_tpu(rng):
    if api.platform() == "tpu":
        pytest.skip("CPU-only assertion")
    params = _mk_qdot_params(rng, 8, 8)
    with pytest.raises(RuntimeError, match="requires a real TPU"):
        api.qdot_packed(params, _mk_acts(rng, 8), backend="pallas")
    qp, xq = _mk_conv(rng, 4, 4)
    with pytest.raises(RuntimeError, match="requires a real TPU"):
        api.qconv(qp, xq, backend="pallas")


def test_env_override(monkeypatch, rng):
    params = _mk_qdot_params(rng, 4, 4)
    x = _mk_acts(rng, 4)
    base = np.asarray(api.qdot(params, x))
    monkeypatch.setenv(api.ENV_VAR, "eager_ref")
    spec = api.resolve("qdot", (16, 256, 128), 4, 4)
    assert spec.name == "eager_ref"
    assert np.array_equal(np.asarray(api.qdot(params, x)), base)
    monkeypatch.setenv(api.ENV_VAR, "not_a_backend")
    with pytest.raises(KeyError, match="not_a_backend"):
        api.qdot(params, x)
    # explicit argument beats the env override
    monkeypatch.setenv(api.ENV_VAR, "eager_ref")
    assert api.resolve("qdot", (16, 256, 128), 4, 4,
                       backend="xla").name == "xla"


def test_registry_table_covers_both_ops():
    rows = api.registry_table()
    assert {(op, b) for op, b, _ in rows} >= {
        (op, b) for op in ("qdot", "qconv")
        for b in ("pallas", "pallas_interpret", "xla", "eager_ref")}


# ---------------------------------------------------- deprecation shims ---

def test_qlinear_apply_use_kernel_shim(rng):
    K, N, M = 288, 64, 50
    w = rng.normal(size=(K, N)).astype(np.float32) * 0.05
    x = np.maximum(rng.normal(size=(M, K)), 0).astype(np.float32) * 0.5
    from repro.core import quantize_linear
    sw = calibrate_weight(jnp.asarray(w), 4)
    sx = calibrate_activation(x, 4, 100.0)
    sy = calibrate_activation(np.maximum(x @ w, 0), 4, 100.0)
    qp = quantize_linear(jnp.asarray(w), sw,
                         np.ones((N,), np.float32),
                         np.zeros((N,), np.float32), sx, sy)
    xq = quantize(jnp.asarray(x), sx)
    with pytest.warns(DeprecationWarning, match="use_kernel"):
        y_old = qlinear_apply(qp, xq, use_kernel=True)
    y_new = api.qdot(qp, xq, backend="pallas_interpret")
    assert np.array_equal(np.asarray(y_old), np.asarray(y_new))
    with pytest.warns(DeprecationWarning):
        y_xla = qlinear_apply(qp, xq, use_kernel=False)
    assert np.array_equal(np.asarray(y_xla),
                          np.asarray(api.qdot(qp, xq, backend="xla")))
    with pytest.raises(ValueError, match="not both"):
        qlinear_apply(qp, xq, backend="xla", use_kernel=True)


def test_qconv2d_apply_use_kernel_shim(rng):
    qp, xq = _mk_conv(rng, 4, 4)
    with pytest.warns(DeprecationWarning, match="use_kernel"):
        y_old = qconv2d_apply(qp, xq, use_kernel=True)
    y_new = api.qconv(qp, xq, backend="pallas_interpret")
    assert np.array_equal(np.asarray(y_old), np.asarray(y_new))


def test_quantconfig_use_kernel_shim():
    with pytest.warns(DeprecationWarning, match="use_kernel"):
        cfg = QuantConfig(mode="int", use_kernel=True)
    assert cfg.backend == "pallas_interpret" and cfg.use_kernel is None
    with pytest.warns(DeprecationWarning):
        cfg = QuantConfig(mode="int", use_kernel=False)
    assert cfg.backend == "xla"
    # new field + deprecated boolean together is contradictory — same
    # policy as the qlinear_apply/qconv2d_apply kwarg shims
    with pytest.raises(ValueError, match="not both"):
        QuantConfig(mode="int", backend="eager_ref", use_kernel=True)
    from repro.deploy.policy import PlanRule
    with pytest.raises(ValueError, match="not both"):
        PlanRule("layers/*", 4, backend="xla", use_kernel=True)
    # normalized shim keeps configs hashable/comparable
    import dataclasses
    assert dataclasses.replace(QuantConfig(backend="xla"), w_bits=4) == \
        QuantConfig(w_bits=4, backend="xla")


OLD_PLAN_JSON = json.dumps({
    "version": 1,
    "default": {"w_bits": 8, "a_bits": 8},
    "rules": [
        {"pattern": "layers/mlp/*", "w_bits": 4, "a_bits": 8,
         "use_kernel": True, "a_absmax": 2.5},
        {"pattern": "layers/attn/*", "w_bits": 2, "a_bits": 8,
         "use_kernel": False, "a_absmax": None},
    ],
    "meta": {"arch": "qwen-smoke"},
})


def test_old_plan_json_single_warning_and_backend_mapping(tmp_path):
    from repro.deploy.policy import (PLAN_VERSION, PrecisionPlan, load_plan,
                                     save_plan)
    with pytest.warns(DeprecationWarning, match="schema-v1") as rec:
        plan = PrecisionPlan.from_json(OLD_PLAN_JSON)
    assert len([w for w in rec if issubclass(
        w.category, DeprecationWarning)]) == 1   # one per artifact
    by_pat = {r.pattern: r for r in plan.rules}
    assert by_pat["layers/mlp/*"].backend == "pallas_interpret"
    assert by_pat["layers/attn/*"].backend == "xla"  # explicit pin kept
    assert by_pat["layers/mlp/*"].w_bits == 4      # not dropped
    # re-save upgrades the artifact: v4, backend field, no use_kernel
    f = tmp_path / "plan.json"
    save_plan(plan, f)
    d = json.loads(f.read_text())
    assert d["version"] == PLAN_VERSION == 4
    assert all("use_kernel" not in r for r in d["rules"])
    assert d["rules"][0]["backend"] == "pallas_interpret"
    with warnings.catch_warnings():
        warnings.simplefilter("error")            # round-trip is clean
        again = load_plan(f)
    assert again == plan


def test_plan_resolve_carries_backend():
    from repro.deploy.policy import PlanRule, PrecisionPlan
    plan = PrecisionPlan(rules=(
        PlanRule("layers/mlp/*", 4, backend="xla"),
        PlanRule("layers/attn/*", 8),
    ))
    base = QuantConfig(mode="int", backend="pallas_interpret")
    assert plan.resolve("layers/mlp/wi", base).backend == "xla"
    # rule without backend inherits the base config's
    assert plan.resolve("layers/attn/wq", base).backend == \
        "pallas_interpret"


def test_unsupported_plan_version_raises():
    from repro.deploy.policy import PrecisionPlan
    with pytest.raises(ValueError, match="unsupported plan version"):
        PrecisionPlan.from_json(json.dumps({"version": 99, "rules": []}))


# -------------------------------------------- _int_matmul dedupe pinned ---

def _old_int_matmul(p, x, qcfg):
    """The pre-registry nn/layers implementation, pinned verbatim as the
    regression oracle for the shared xla_int_gemm path."""
    absmax = qcfg.a_absmax or 4.0
    a_max = packing.int_range(qcfg.a_bits, True)[1]
    a_scale = absmax / a_max
    x_q = jnp.clip(jnp.round(x.astype(jnp.float32) / a_scale), -a_max,
                   a_max).astype(jnp.int8)
    x_q = packing.pad_to_chunk(x_q, axis=-1)
    w_int = packing.unpack(p["w_packed"], qcfg.w_bits, True, axis=0)
    acc = jax.lax.dot_general(
        x_q, w_int, (((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    scale = (p["w_scale"] * a_scale).astype(jnp.float32)
    return (acc.astype(jnp.float32) * scale).astype(x.dtype)


@pytest.mark.parametrize("wb", BITS)
@pytest.mark.parametrize("ab", BITS)
def test_dense_int_matmul_matches_old_implementation(ab, wb, rng):
    w = (rng.normal(size=(96, 48)) * 0.1).astype(np.float32)
    x = rng.normal(size=(4, 96)).astype(np.float32)
    packed, scale = pack_dense_weights(jnp.asarray(w), wb)
    p = {"w_packed": packed, "w_scale": scale}
    qcfg = QuantConfig(mode="int", w_bits=wb, a_bits=ab, a_absmax=4.0)
    got = np.asarray(dense_apply(p, jnp.asarray(x), qcfg=qcfg))
    want = np.asarray(_old_int_matmul(p, jnp.asarray(x), qcfg))
    np.testing.assert_array_equal(got, want)


# ----------------------------------------------------------- tune cache ---

def test_tune_cache_roundtrip(tmp_path):
    tune.clear()
    try:
        tune.record_block("qdot", (64, 256, 256), 4, 4,
                          "pallas_interpret", (32, 128, 128))
        assert tune.get_block("qdot", (64, 256, 256), 4, 4,
                              "pallas_interpret") == (32, 128, 128)
        assert tune.get_block("qdot", (64, 256, 256), 4, 2,
                              "pallas_interpret") is None
        f = tmp_path / "tune.json"
        tune.save(f)
        tune.clear()
        assert tune.get_block("qdot", (64, 256, 256), 4, 4,
                              "pallas_interpret") is None
        tune.merge(tune.load(f))
        assert tune.get_block("qdot", (64, 256, 256), 4, 4,
                              "pallas_interpret") == (32, 128, 128)
        with pytest.raises(ValueError, match="version"):
            tune.TuneCache.from_json('{"version": 42}')
    finally:
        tune.clear()


def test_qdot_uses_cached_block_and_stays_bit_exact(rng):
    """A cached (valid, non-default) block is consumed by api.qdot and the
    result stays bit-exact vs eager_ref."""
    params = _mk_qdot_params(rng, 4, 4, K=512, N=256)
    x = _mk_acts(rng, 4, M=64, K=512)
    want = np.asarray(api.qdot(params, x, backend="eager_ref"))
    tune.clear()
    try:
        tune.record_block("qdot", (64, 512, 256), 4, 4,
                          "pallas_interpret", (32, 128, 256))
        got = np.asarray(api.qdot(params, x, backend="pallas_interpret"))
        assert np.array_equal(got, want)
    finally:
        tune.clear()


@pytest.mark.slow
def test_autotune_qdot_records_best_block(rng):
    tune.clear()
    try:
        params = _mk_qdot_params(rng, 4, 4)
        x2 = packing.pack(_mk_acts(rng, 4, M=32), 4, axis=-1)
        blk, pipe = tune.autotune_qdot(params, x2,
                                       backend="pallas_interpret", iters=1)
        assert tune.get_block("qdot", (32, 256, 128), 4, 4,
                              "pallas_interpret") == blk
        assert tune.get_pipeline("qdot", (32, 256, 128), 4, 4,
                                 "pallas_interpret") == pipe
    finally:
        tune.clear()
