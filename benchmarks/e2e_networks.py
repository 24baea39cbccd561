"""Network-level QNN benchmark — the paper's fig. 11 story composed into
whole CNNs (BENCH_e2e.json).

The paper's headline is network-level: conv layers at W{8,4,2} composed
into full QNNs running on the parallel cluster. This benchmark runs the
two paper-class networks of `repro.vision` (MobileNetV1-style
depthwise-separable, MLPerf-Tiny-style ResNet-8) end to end as integer
images — per-layer wall time at one device, whole-network wall time
across 1..8-device meshes (images data-parallel, the serving analogue of
fig. 9), at uniform W8/W4/W2 plus the planner-produced mixed plan, per
kernel backend. Mesh results are asserted bit-exact against the
single-device forward before timing (the registry's psum-free
construction). CPU wall time is structure-comparative only; total rows
carry the analytic v5e roofline projection alongside (benchmarks/common).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m benchmarks.e2e_networks --json BENCH_e2e.json
"""
import argparse
import json
import os
import sys

# must precede the first jax import to materialize host-platform devices
if "jax" not in sys.modules and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import HBM_BW, PEAK_FLOPS, emit, time_call
from repro.deploy.calibrate import calibrate_vision
from repro.deploy.planner import auto_budget, plan_mixed_precision
from repro.parallel.ctx import make_mesh
from repro.vision.configs import get_vision_config
from repro.vision.models import (forward_int, init_fp, quantize_input,
                                 quantize_net, streamed_weight_bytes,
                                 trace_shapes)

BATCH = 8


def _layer_macs(t) -> int:
    """MACs per image for one traced layer (0 for pool/add)."""
    L, (h, w, c), (oh, ow, oc) = t["layer"], t["in"], t["out"]
    if L.kind == "conv":
        return oh * ow * oc * L.fh * L.fw * c
    if L.kind == "dwconv":
        return oh * ow * c * L.fh * L.fw
    if L.kind == "linear":
        return c * L.cout
    return 0


def _quantized_nets(cfg, fp_params, bits_sweep, rng, backend):
    """(tag, qnet) per sweep point: uniform W{b} plus the planner plan."""
    stats, absmax = calibrate_vision(
        cfg, fp_params,
        [rng.uniform(0, 1, (4, *cfg.in_hw, cfg.in_ch)).astype(np.float32)])
    out = [(str(b), quantize_net(cfg, fp_params, absmax, default_w_bits=b,
                                 backend=backend))
           for b in bits_sweep]
    plan = plan_mixed_precision(stats, auto_budget(stats), backend=backend)
    out.append(("mixed", quantize_net(cfg, fp_params, absmax, plan=plan,
                                      backend=backend)))
    return out


def _per_layer_rows(net, tag, qnet, x_hat, backend, rows):
    """Time each layer on its real intermediate input (1 device)."""
    trace = {t["layer"].path: t for t in trace_shapes(qnet.cfg)}
    stream, edges = x_hat, {}
    for L, q in qnet.qlayers:
        xin = edges[L.input_from] if L.input_from else stream
        if L.kind in ("conv", "dwconv", "linear"):
            fn = jax.jit(lambda v, q=q: q.apply(v, backend=backend))
            args = (xin,)
        elif L.kind == "add":
            fn = jax.jit(lambda a, b, q=q: q.apply(a, b))
            args = (xin, edges[L.skip_from])
        else:
            fn = jax.jit(lambda v, q=q: q.apply(v))
            args = (xin,)
        us = time_call(fn, *args)
        macs = _layer_macs(trace[L.path])
        rows.append({"name": f"e2e_{net}_{tag}_{L.path}_dev1",
                     "net": net, "layer": L.path, "bits": tag,
                     "devices": 1, "us_per_call": round(float(us), 1),
                     "macs_per_image": macs})
        emit(f"e2e_{net}_{tag}_{L.path}_dev1", us,
             f"macs={macs}", backend or "default")
        y = fn(*args)
        if L.save_as:
            edges[L.save_as] = y
        if not L.branch:
            stream = y


def _lm_planner_rows(rows, rng, backend):
    """Fine-grain vs per-layer planner rows on the LM dense path.

    The vision nets quantize per tensor (no segment support), so the
    fine-grain comparison runs on the transformer zoo's smoke LM — the
    one forward whose dense path consumes `PlanRule.segments` end to
    end. The smoke config is widened to d_ff=384 so the MLP projections
    span 3 channel groups (d_out=128 would degenerate to one group and
    the best-of-both planner would return the layer plan verbatim).
    Both plans run at the SAME auto budget; the row pair's
    bytes_streamed delta is the fine-grain packing win."""
    import dataclasses

    from repro.configs.qwen2p5_3b import smoke_config
    from repro.deploy.apply import (apply_plan, dense_inventory,
                                    quantized_dense_paths)
    from repro.deploy.calibrate import calibrate
    from repro.models.api import Model
    from repro.nn.layers import QuantConfig

    cfg = dataclasses.replace(smoke_config(), d_model=128, d_ff=384)
    fp = Model(cfg)
    fp_params = fp.init(jax.random.PRNGKey(0))
    seq = 16
    batches = [rng.integers(2, cfg.vocab, size=(2, seq)).astype(np.int32)]
    stats = calibrate(fp, fp_params, batches)
    # a tight budget is where granularity pays: whole-layer demotions bust
    # it, channel-group demotions fit (frac=0.5 admits every whole-layer
    # move and the plans converge)
    budget = auto_budget(stats, frac=0.12)
    plans = [("planner-layer",
              plan_mixed_precision(stats, budget, backend=backend,
                                   granularity="layer")),
             ("planner-fine",
              plan_mixed_precision(stats, budget, backend=backend,
                                   granularity="channel_group"))]
    qint = QuantConfig(mode="int", w_bits=8, a_bits=8)
    q0 = Model(dataclasses.replace(cfg, quant=qint))
    inv = dense_inventory(fp_params, quantized_dense_paths(q0.defs()))
    macs = sum(L * k * n for (L, k, n) in inv.values()) * seq
    toks = jnp.asarray(batches[0])
    for tag, plan in plans:
        q = Model(dataclasses.replace(cfg, quant=qint, quant_plan=plan))
        q_params = apply_plan(q.init(jax.random.PRNGKey(0)), fp_params, plan)
        fn = jax.jit(lambda p, t, q=q: q.forward(p, {"tokens": t})[0])
        us = time_call(fn, q_params, toks)
        packed_b = plan.meta["packed_weight_bytes"]
        n_seg = sum(1 for r in plan.rules if r.segments is not None)
        rows.append({"name": f"e2e_qwen-smoke_{tag}_total_dev1",
                     "net": "qwen-smoke", "layer": "total", "bits": tag,
                     "devices": 1, "us_per_call": round(float(us), 1),
                     "macs_per_image": macs, "bytes_streamed": packed_b})
        emit(f"e2e_qwen-smoke_{tag}_total_dev1", us,
             f"bytes={packed_b};segmented_rules={n_seg};macs={macs}",
             backend or "default")


def main(nets=("mobilenet-tiny", "resnet8"), bits_sweep=(8, 4, 2),
         devices=None, backend=None, json_path="BENCH_e2e.json",
         smoke=False, per_layer=True, lm_planner=True):
    avail = len(jax.devices())
    if devices is None:
        devices = [d for d in (1, 2, 4, 8) if d <= avail]
    rng = np.random.default_rng(0)
    rows = []
    for net in nets:
        cfg = get_vision_config(net, smoke=smoke)
        fp_params = init_fp(cfg, seed=0)
        total_macs = sum(_layer_macs(t) for t in trace_shapes(cfg))
        images = rng.uniform(0, 1, (BATCH, *cfg.in_hw, cfg.in_ch)
                             ).astype(np.float32)
        for tag, qnet in _quantized_nets(cfg, fp_params, bits_sweep, rng,
                                         backend):
            x_hat = quantize_input(qnet, images)
            if per_layer:
                _per_layer_rows(net, tag, qnet, x_hat, backend, rows)
            ref = np.asarray(forward_int(qnet, x_hat, backend=backend))
            # memory-roofline term: bytes one forward streams (the qdot
            # route's packed weights + epilogue vectors), NOT the full
            # artifact — which materializes both depthwise lowerings
            packed_b = streamed_weight_bytes(qnet)
            measured = []
            for n_dev in devices:
                if n_dev > avail:
                    print(f"# e2e: skipping {n_dev} devices "
                          f"(only {avail} available)")
                    continue
                mesh = (None if n_dev == 1 else make_mesh(
                    (n_dev, 1), ("data", "model"),
                    devices=jax.devices()[:n_dev]))
                fn = jax.jit(lambda xh, q=qnet, m=mesh: forward_int(
                    q, xh, backend=backend, mesh=m))
                got = np.asarray(fn(x_hat))
                assert np.array_equal(got, ref), \
                    f"{net} {tag}: mesh result diverged at {n_dev} devices"
                measured.append((n_dev, time_call(fn, x_hat)))
            if not measured:
                continue
            base_us = min(measured)[1]
            for n_dev, us in measured:
                speedup = base_us / us if us > 0 else float("nan")
                flops = 2 * total_macs * BATCH / n_dev
                t_proj = max(flops / PEAK_FLOPS, packed_b / HBM_BW)
                rows.append({
                    "name": f"e2e_{net}_{tag}_total_dev{n_dev}",
                    "net": net, "layer": "total", "bits": tag,
                    "devices": n_dev,
                    "us_per_call": round(float(us), 1),
                    "speedup": round(float(speedup), 3),
                    "efficiency": round(float(speedup) / n_dev, 3),
                    "macs_per_image": total_macs,
                    "bytes_streamed": packed_b,
                    "proj_us_v5e": round(t_proj * 1e6, 3)})
                emit(f"e2e_{net}_{tag}_total_dev{n_dev}", us,
                     f"speedup={speedup:.2f};bytes={packed_b};"
                     f"proj_us_v5e={t_proj * 1e6:.3f}",
                     backend or "default")
    if lm_planner:
        _lm_planner_rows(rows, rng, backend)
    if json_path and rows:
        payload = {"version": 1, "batch": BATCH,
                   "path": "repro.vision.models.forward_int",
                   "rows": rows}
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(rows)} rows -> {json_path}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--nets", default="mobilenet-tiny,resnet8")
    ap.add_argument("--bits", default="8,4,2",
                    help="uniform w_bits sweep (the planner-mixed point "
                         "always runs)")
    ap.add_argument("--devices", default=None,
                    help="comma-separated mesh sizes (default: 1,2,4,8 "
                         "capped at available)")
    ap.add_argument("--backend", default=None)
    ap.add_argument("--json", default="BENCH_e2e.json")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke-size nets (CI/laptop)")
    ap.add_argument("--no-per-layer", action="store_true")
    ap.add_argument("--no-lm-planner", action="store_true",
                    help="skip the transformer fine-grain vs per-layer "
                         "planner rows")
    args = ap.parse_args()
    main(nets=tuple(args.nets.split(",")),
         bits_sweep=tuple(int(b) for b in args.bits.split(",")),
         devices=(None if args.devices is None else
                  [int(v) for v in args.devices.split(",")]),
         backend=args.backend, json_path=args.json, smoke=args.smoke,
         per_layer=not args.no_per_layer,
         lm_planner=not args.no_lm_planner)
