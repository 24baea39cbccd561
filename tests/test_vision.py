"""The end-to-end quantized CNN subsystem (`repro.vision`).

* graph/trace sanity for both paper-class nets (MobileNetV1-style,
  MLPerf-Tiny-style ResNet-8);
* whole-network bit-exactness across kernel backends ({xla,
  pallas_interpret}), across mesh vs single-device, under uniform W8A8
  and a planner-produced mixed W{8,4,2} plan (the ISSUE-5 acceptance
  criterion), and across a plan-JSON round-trip;
* layer-boundary requantization edges: uint2/uint4 saturation, avg-pool
  floor rounding vs an int64 oracle, residual-add saturation vs an int64
  oracle, grid-preserving max pool;
* depthwise lowering: block-diagonal im2col+qdot vs per-group qconv vs
  an independent numpy depthwise oracle, all bit-exact;
* the conv calibration tap (`calibrate_vision`) and the VisionEngine's
  wave sharding/utilization accounting.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.calibration import calibrate_weight
from repro.core.quantize import QuantSpec, quantize, requantize_shift_i64
from repro.deploy.calibrate import calibrate_vision
from repro.deploy.planner import auto_budget, plan_mixed_precision
from repro.deploy.policy import PlanRule, PrecisionPlan, load_plan, save_plan
from repro.parallel.ctx import make_mesh
from repro.vision import layers as vl
from repro.vision.configs import get_vision_config
from repro.vision.models import (collect_absmax, forward_fp, forward_int,
                                 init_fp, quantize_input, quantize_net,
                                 trace_shapes, vision_artifact_bytes)

NETS = ("resnet8", "mobilenet-tiny")


@pytest.fixture(scope="module")
def art():
    """Per-net calibrated fp artifact: (cfg, params, stats, absmax, x)."""
    out = {}
    rng = np.random.default_rng(0)
    for name in NETS:
        cfg = get_vision_config(name, smoke=True)
        params = init_fp(cfg, seed=0)
        x = rng.uniform(0, 1, size=(4, *cfg.in_hw, cfg.in_ch)).astype(
            np.float32)
        stats, absmax = calibrate_vision(cfg, params, [x])
        out[name] = (cfg, params, stats, absmax, x)
    return out


# --------------------------------------------------------------- graph ---

@pytest.mark.parametrize("net", NETS)
def test_trace_and_fp_forward(net, art):
    cfg, params, _, _, x = art[net]
    trace = trace_shapes(cfg)
    assert trace[-1]["out"] == (0, 0, cfg.num_classes)
    kinds = {t["layer"].kind for t in trace}
    assert {"conv", "avgpool_global", "linear"} <= kinds
    y = forward_fp(cfg, params, jnp.asarray(x))
    assert y.shape == (4, cfg.num_classes)
    assert bool(jnp.all(jnp.isfinite(y)))


# ------------------------------------------------- network bit-exactness ---

@pytest.mark.parametrize("net", NETS)
def test_backend_parity_uniform_w8a8(net, art):
    """Whole-net forward is bit-exact across {xla, pallas_interpret} at
    every integer edge, under uniform W8A8."""
    cfg, params, _, absmax, x = art[net]
    qnet = quantize_net(cfg, params, absmax)
    x_hat = quantize_input(qnet, x)
    edges = {}
    for be in ("xla", "pallas_interpret"):
        seen = []
        out = forward_int(qnet, x_hat, backend=be,
                          collect=lambda p, y: seen.append((p, np.asarray(y))))
        edges[be] = dict(seen)
        assert out.dtype == jnp.int32 and out.shape == (4, cfg.num_classes)
    assert edges["xla"].keys() == edges["pallas_interpret"].keys()
    for path in edges["xla"]:
        assert np.array_equal(edges["xla"][path],
                              edges["pallas_interpret"][path]), path


@pytest.mark.parametrize("net", NETS)
def test_backend_parity_mixed_plan(net, art):
    """Planner-produced mixed W{8,4,2} plan: bit-exact across backends,
    smaller artifact than uniform W8."""
    cfg, params, stats, absmax, x = art[net]
    plan = plan_mixed_precision(stats, auto_budget(stats))
    qnet = quantize_net(cfg, params, absmax, plan=plan)
    q8 = quantize_net(cfg, params, absmax)
    assert vision_artifact_bytes(qnet) < vision_artifact_bytes(q8)
    bits = set(qnet.layer_bits().values())
    assert bits <= {8, 4, 2} and len(bits) >= 1
    x_hat = quantize_input(qnet, x)
    a = np.asarray(forward_int(qnet, x_hat, backend="xla"))
    b = np.asarray(forward_int(qnet, x_hat, backend="pallas_interpret"))
    assert np.array_equal(a, b)


@pytest.mark.parametrize("net", NETS)
@pytest.mark.parametrize("mixed", [False, True])
def test_mesh_parity(net, mixed, art):
    """Mesh-sharded forward (images DP over a 4-device cluster, ragged
    batch) is bit-exact vs meshless, uniform and mixed."""
    cfg, params, stats, absmax, x = art[net]
    plan = (plan_mixed_precision(stats, auto_budget(stats)) if mixed
            else None)
    qnet = quantize_net(cfg, params, absmax, plan=plan)
    x5 = np.concatenate([x, x[:1]], axis=0)        # 5 % 4 != 0: pad path
    x_hat = quantize_input(qnet, x5)
    ref = np.asarray(forward_int(qnet, x_hat, backend="xla"))
    mesh = make_mesh((4, 1), ("data", "model"),
                     devices=jax.devices()[:4])
    got = np.asarray(forward_int(qnet, x_hat, backend="xla", mesh=mesh))
    assert np.array_equal(ref, got)


def test_plan_json_roundtrip(tmp_path, art):
    cfg, params, stats, absmax, x = art["resnet8"]
    plan = plan_mixed_precision(stats, auto_budget(stats))
    save_plan(plan, tmp_path / "vplan.json")
    plan2 = load_plan(tmp_path / "vplan.json")
    q1 = quantize_net(cfg, params, absmax, plan=plan)
    q2 = quantize_net(cfg, params, absmax, plan=plan2)
    x_hat = quantize_input(q1, x)
    assert np.array_equal(np.asarray(forward_int(q1, x_hat, backend="xla")),
                          np.asarray(forward_int(q2, x_hat, backend="xla")))


def test_plan_rules_route_backends(art):
    """A plan rule's ``backend`` lands on the matching layers and is used
    unless the call site overrides it."""
    cfg, params, _, absmax, _ = art["resnet8"]
    plan = PrecisionPlan(rules=(
        PlanRule(pattern="s2/*", w_bits=4, backend="pallas_interpret"),))
    qnet = quantize_net(cfg, params, absmax, plan=plan)
    routed = {L.path: getattr(q, "backend", None)
              for L, q in qnet.qlayers if L.kind in ("conv", "dwconv")}
    assert routed["s2/c1"] == "pallas_interpret"
    assert routed["stem"] is None


# ---------------------------------------------- boundary requantization ---

@pytest.mark.parametrize("a_bits", [4, 2])
def test_sub_byte_boundaries_saturate(a_bits, art):
    """uint{4,2} end-to-end: every activation edge stays on the unsigned
    grid and the net still discriminates inputs."""
    _, _, _, _, x = art["resnet8"]
    cfg = get_vision_config("resnet8", smoke=True, a_bits=a_bits)
    params = init_fp(cfg, seed=0)
    absmax = collect_absmax(cfg, params, [x])
    qnet = quantize_net(cfg, params, absmax)
    x_hat = quantize_input(qnet, x)
    hi = packing.int_range(a_bits, False)[1]
    seen = {}
    forward_int(qnet, x_hat, backend="xla",
                collect=lambda p, y: seen.update({p: np.asarray(y)}))
    for path, y in seen.items():
        if path == "head":
            continue  # raw int32 logits, not an activation edge
        assert y.min() >= 0 and y.max() <= hi, (path, y.min(), y.max())
    # at least one edge actually reaches the grid ceiling (saturation is
    # exercised, not vacuously passed)
    assert any(y.max() == hi for p, y in seen.items() if p != "head")


def test_avgpool_global_floor_rounding_vs_oracle(rng):
    """Global avg pool requant == int64 floor oracle, element-exact."""
    x = rng.integers(0, 256, size=(3, 8, 8, 16)).astype(np.int32)
    x = np.clip(x, 0, 127).astype(np.int8)
    m, d = vl.fold_avgpool_requant(64, 0.031, 0.017)
    pool = vl.QAvgPool2D(window=0, stride=1, m=m, d=d, out_bits=8)
    got = np.asarray(pool.apply(jnp.asarray(x)))
    s = x.astype(np.int64).sum(axis=(1, 2))
    want = np.clip(requantize_shift_i64(s, m, d), 0, 127)
    assert np.array_equal(got, want.astype(np.int8))


def test_avgpool_windowed_vs_oracle(rng):
    x = rng.integers(0, 16, size=(2, 6, 6, 8)).astype(np.int8)
    m, d = vl.fold_avgpool_requant(4, 0.02, 0.03)
    pool = vl.QAvgPool2D(window=2, stride=2, m=m, d=d, out_bits=4)
    got = np.asarray(pool.apply(jnp.asarray(x)))
    xs = x.astype(np.int64)
    s = (xs[:, 0::2, 0::2] + xs[:, 1::2, 0::2]
         + xs[:, 0::2, 1::2] + xs[:, 1::2, 1::2])
    want = np.clip(requantize_shift_i64(s, m, d), 0, 15)
    assert np.array_equal(got, want.astype(np.int8))


@pytest.mark.parametrize("out_bits", [8, 4, 2])
def test_residual_add_saturates_and_matches_oracle(out_bits, rng):
    """Two-scale integer add: exact vs the int64 oracle, and the clip
    actually saturates at the uint{8,4,2} ceiling for hot inputs."""
    hi_in = packing.int_range(8, False)[1]
    a = rng.integers(0, hi_in + 1, size=(2, 4, 4, 8)).astype(np.int8)
    b = rng.integers(0, hi_in + 1, size=(2, 4, 4, 8)).astype(np.int8)
    a[0, 0, 0, :] = hi_in          # force the saturating corner
    b[0, 0, 0, :] = hi_in
    m1, m2, d = vl.fold_add_requant(0.04, 0.03, 0.02)
    add = vl.QResidualAdd(m1=m1, m2=m2, d=d, out_bits=out_bits)
    got = np.asarray(add.apply(jnp.asarray(a), jnp.asarray(b)))
    hi = packing.int_range(out_bits, False)[1]
    want = np.clip((a.astype(np.int64) * m1 + b.astype(np.int64) * m2) >> d,
                   0, hi)
    assert np.array_equal(got, want.astype(np.int8))
    assert got.max() == hi         # the hot corner saturated


def test_maxpool_is_grid_preserving(rng):
    """Integer max pool == pooling the dequantized values then
    re-quantizing: order-preserving, so no requant params exist."""
    spec = QuantSpec.activation(4, 3.0)
    x = rng.integers(0, 16, size=(2, 8, 8, 4)).astype(np.int8)
    pool = vl.QMaxPool2D(window=2, stride=2)
    got = np.asarray(pool.apply(jnp.asarray(x)))
    xs = x
    want = np.maximum.reduce([xs[:, 0::2, 0::2], xs[:, 1::2, 0::2],
                              xs[:, 0::2, 1::2], xs[:, 1::2, 1::2]])
    assert np.array_equal(got, want)
    assert got.max() <= spec.int_max


# ------------------------------------------------------------ depthwise ---

def _dw_oracle(x, w_hat, kappa, lam, m, d, out_bits, stride, padding):
    """Independent numpy depthwise conv + eq.3/4 epilogue (int64)."""
    n, h, wd, c = x.shape
    fh, fw, _ = w_hat.shape
    xp = np.zeros((n, h + 2 * padding, wd + 2 * padding, c), np.int64)
    xp[:, padding:padding + h, padding:padding + wd] = x
    oh = (h + 2 * padding - fh) // stride + 1
    ow = (wd + 2 * padding - fw) // stride + 1
    phi = np.zeros((n, oh, ow, c), np.int64)
    for dy in range(fh):
        for dx in range(fw):
            sl = xp[:, dy:dy + stride * oh:stride,
                    dx:dx + stride * ow:stride]
            phi += sl * w_hat[dy, dx].astype(np.int64)
    phi_p = phi * kappa.astype(np.int64) + lam.astype(np.int64)
    y = requantize_shift_i64(phi_p, m.astype(np.int64), d)
    hi = packing.int_range(out_bits, False)[1]
    return np.clip(y, 0, hi).astype(np.int8)


@pytest.mark.parametrize("wb", [8, 4, 2])
def test_depthwise_lowerings_bit_exact(wb, rng):
    """qdot (block-diagonal) and per_group lowerings agree with each
    other and with the numpy depthwise oracle, per bit-width."""
    c, h = 8, 6
    p = {"w": jnp.asarray(rng.normal(size=(3, 3, c)).astype(np.float32)
                          * 0.4),
         "bn_scale": jnp.asarray((rng.normal(size=(c,)) * 0.05 + 0.4
                                  ).astype(np.float32)),
         "bn_bias": jnp.asarray((rng.normal(size=(c,)) * 0.02
                                 ).astype(np.float32))}
    spec_x = QuantSpec.activation(8, 2.0)
    spec_y = QuantSpec.activation(8, 1.5)
    dw = vl.quantize_depthwise(p, spec_x, spec_y, wb, stride=2, padding=1)
    x = rng.integers(0, 128, size=(2, h, h, c)).astype(np.int8)
    xj = jnp.asarray(x)
    got_qdot = np.asarray(dw.apply(xj, backend="xla", lowering="qdot"))
    got_pg = np.asarray(dw.apply(xj, backend="xla", lowering="per_group"))
    got_pg_pal = np.asarray(dw.apply(xj, backend="pallas_interpret",
                                     lowering="per_group"))
    w_hat = np.asarray(quantize(p["w"], calibrate_weight(p["w"], wb)))
    g = dw.gemm
    want = _dw_oracle(x, w_hat, np.asarray(g.kappa), np.asarray(g.lam),
                      np.asarray(g.m), g.d, g.out_bits, 2, 1)
    assert np.array_equal(got_qdot, want)
    assert np.array_equal(got_pg, want)
    assert np.array_equal(got_pg_pal, want)


def test_depthwise_auto_lowering_and_errors(rng):
    p = {"w": jnp.ones((3, 3, 4), jnp.float32) * 0.1,
         "bn_scale": jnp.ones((4,), jnp.float32),
         "bn_bias": jnp.zeros((4,), jnp.float32)}
    spec = QuantSpec.activation(8, 2.0)
    dw = vl.quantize_depthwise(p, spec, spec, 8, stride=1, padding=1)
    x = jnp.zeros((1, 4, 4, 4), jnp.int8)
    with pytest.raises(ValueError, match="unknown depthwise lowering"):
        dw.apply(x, lowering="nope")
    # auto under an explicit pallas-family backend takes the per-group
    # fused route; under xla the single block-diagonal GEMM
    assert dw._auto_lowering(x, "pallas_interpret") == "per_group"
    assert dw._auto_lowering(x, "xla") == "qdot"


# ----------------------------------------------------------- calibration ---

def test_calibrate_vision_stats(art):
    cfg, params, stats, absmax, _ = art["resnet8"]
    compute_paths = {t["layer"].path for t in trace_shapes(cfg)
                     if t["layer"].kind in ("conv", "dwconv", "linear")}
    assert set(stats) == compute_paths
    for path, st in stats.items():
        assert st.taps > 0 and st.a_absmax > 0, path
        assert st.sens(2) > st.sens(8) >= 0, path
    requant_paths = {t["layer"].path for t in trace_shapes(cfg)
                     if t["layer"].kind in ("conv", "dwconv",
                                            "avgpool_global", "add")}
    assert requant_paths <= set(absmax)
    assert "__input__" in absmax


def test_conv_tap_restores_previous():
    calls = []
    with vl.conv_tap(lambda p, x: calls.append("a")):
        with vl.conv_tap(lambda p, x: calls.append("b")):
            vl.linear_fp({"w": jnp.ones((2, 2))}, jnp.ones((1, 2)))
        vl.linear_fp({"w": jnp.ones((2, 2))}, jnp.ones((1, 2)))
    vl.linear_fp({"w": jnp.ones((2, 2))}, jnp.ones((1, 2)))
    assert calls == ["b", "a"]


def test_quantize_net_missing_absmax_raises(art):
    cfg, params, _, absmax, _ = art["resnet8"]
    partial = {k: v for k, v in absmax.items() if k != "s2/c1"}
    with pytest.raises(KeyError, match="s2/c1"):
        quantize_net(cfg, params, partial)


# --------------------------------------------------------------- engine ---

def test_vision_engine_waves_and_utilization(art):
    """Ragged 6-request list in waves of 4 on a dp=2 mesh: outputs equal
    the meshless forward and the utilization means are exact."""
    from repro.serve.engine import VisionEngine

    cfg, params, _, absmax, x = art["resnet8"]
    qnet = quantize_net(cfg, params, absmax)
    rng = np.random.default_rng(3)
    images = rng.uniform(0, 1, size=(6, *cfg.in_hw, cfg.in_ch)).astype(
        np.float32)
    mesh = make_mesh((2, 1), ("data", "model"),
                     devices=jax.devices()[:2])
    eng = VisionEngine(qnet, batch_size=4, mesh=mesh, backend="xla")
    got = eng.run(images)
    want = np.asarray(forward_int(
        qnet, quantize_input(qnet, images), backend="xla"))
    assert np.array_equal(got, want)
    rep = eng.utilization_report()
    # wave 1: 4/4 real -> [1, 1]; wave 2: 2/4 -> [1, 0]
    assert rep["waves"] == 2 and rep["devices"] == 2
    assert rep["per_device"] == [1.0, 0.5]
    assert rep["mean_util"] == pytest.approx(0.75)
    assert eng.artifact_bytes() == vision_artifact_bytes(qnet)


def test_vision_engine_ragged_batch_over_dp(art):
    """batch_size % dp != 0 no longer raises: the slot array is padded
    to whole per-device blocks and results still equal the meshless
    forward (the pads never reach admission)."""
    from repro.serve.engine import VisionEngine

    cfg, params, _, absmax, _ = art["resnet8"]
    qnet = quantize_net(cfg, params, absmax)
    rng = np.random.default_rng(5)
    images = rng.uniform(0, 1, size=(5, *cfg.in_hw, cfg.in_ch)).astype(
        np.float32)
    mesh = make_mesh((4, 1), ("data", "model"),
                     devices=jax.devices()[:4])
    eng = VisionEngine(qnet, batch_size=3, mesh=mesh, backend="xla")
    got = eng.run(images)
    want = np.asarray(forward_int(
        qnet, quantize_input(qnet, images), backend="xla"))
    assert np.array_equal(got, want)
    assert eng.utilization_report()["devices"] == 4


# ------------------------------------------------------------ CLI (slow) ---

@pytest.mark.slow
def test_vision_cli(tmp_path):
    from tests.test_launchers import _run

    plan = tmp_path / "vplan.json"
    r = _run(["repro.launch.vision", "--net", "resnet8", "--smoke",
              "--budget", "auto", "--out", str(plan)])
    assert "vision deploy done" in r.stdout, r.stderr[-1500:]
    assert plan.exists()
    r2 = _run(["repro.launch.vision", "--net", "resnet8", "--smoke",
               "--from-plan", str(plan)])
    assert "vision deploy done" in r2.stdout, r2.stderr[-1500:]


@pytest.mark.slow
def test_e2e_benchmark_smoke(tmp_path):
    import json

    from tests.test_launchers import _run

    out = tmp_path / "BENCH_e2e.json"
    r = _run(["benchmarks.e2e_networks", "--smoke", "--nets", "resnet8",
              "--bits", "8", "--devices", "1,2", "--json", str(out),
              "--no-per-layer"],
             extra_env={"XLA_FLAGS":
                        "--xla_force_host_platform_device_count=8"})
    assert out.exists(), r.stderr[-1500:]
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks import schema
    schema.validate_file(out)           # the checked-in artifact schema
    rows = json.load(open(out))["rows"]
    totals = [row for row in rows if row["layer"] == "total"]
    assert {row["devices"] for row in totals} == {1, 2}
    assert all("us_per_call" in row and "bits" in row for row in rows)
    # the planner-mixed point always rides along the uniform sweep
    assert {row["bits"] for row in totals} == {"8", "mixed"}
