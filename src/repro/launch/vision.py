"""Vision deployment launcher: calibrate -> plan -> pack -> serve a CNN.

The CNN analogue of `repro.launch.deploy` + `repro.launch.serve` in one
CLI: build a paper-class network (`repro.vision.configs`), calibrate it
on images (random in --smoke runs), search a per-layer W{8,4,2} plan,
pack the integer artifact, and serve an image batch through the
`VisionEngine` (optionally mesh-sharded):

    PYTHONPATH=src python -m repro.launch.vision --net resnet8 --smoke \
        --budget auto --out vplan.json --requests 6 --batch 4

``--from-plan plan.json`` skips calibration/search and re-packs from an
existing plan artifact (the round-trip CI exercises); ``--mesh dp,tp``
serves waves data-parallel on a device mesh (tp shards conv output
channels inside the kernels when it divides them).
"""
from __future__ import annotations

import argparse


def calibrate_and_plan(cfg, fp_params, batches, *, candidates,
                       budget="auto", a_bits: int = 8, backend=None,
                       smoke: bool = False):
    """Calibrate ``cfg`` on image ``batches`` and search a per-layer
    W{candidates} plan under ``budget`` ('auto' or a float). Prints one
    line per layer; returns ``(plan, absmax)``."""
    from repro.deploy.calibrate import calibrate_vision
    from repro.deploy.planner import auto_budget, plan_mixed_precision
    from repro.obs import trace as obs

    print(f"calibrating {cfg.name}: {len(batches)} batches of "
          f"{len(batches[0])} images {cfg.in_hw}, candidates W{candidates}")
    with obs.span("deploy.calibrate", cat="deploy", arch=cfg.name,
                  batches=len(batches), candidates=candidates):
        stats, absmax = calibrate_vision(cfg, fp_params, batches,
                                         bits=candidates)
    with obs.span("deploy.plan", cat="deploy", arch=cfg.name,
                  paths=len(stats)):
        budget = (auto_budget(stats, candidates) if budget == "auto"
                  else float(budget))
        plan = plan_mixed_precision(
            stats, budget, candidates=candidates, a_bits=a_bits,
            backend=backend, meta={"arch": cfg.name, "smoke": smoke})
    for r in plan.rules:
        st = stats[r.pattern]
        print(f"  {r.pattern:<16} W{r.w_bits}A{r.a_bits}  "
              f"absmax={st.a_absmax:.3f}  sens="
              f"{{{', '.join(f'{b}:{st.sens(b):.2e}' for b in candidates)}}}")
    return plan, absmax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", required=True,
                    help="vision config name (repro.vision.configs)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--a-bits", type=int, default=8,
                    help="activation bits at every layer boundary")
    ap.add_argument("--bits", default="8,4,2",
                    help="candidate w_bits, widest first")
    ap.add_argument("--budget", default="auto")
    ap.add_argument("--backend", default=None,
                    help="kernel backend the net routes through "
                         "(repro.kernels.api; default: registry)")
    ap.add_argument("--from-plan", default=None,
                    help="existing plan JSON: skip calibrate/search")
    ap.add_argument("--out", default="vision_plan.json")
    ap.add_argument("--calib-batches", type=int, default=2)
    ap.add_argument("--calib-batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="serve on a (data=DP, model=TP) device mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    # heavy imports after argparse so --help stays instant
    import jax
    import numpy as np

    from repro.deploy.policy import load_plan, save_plan
    from repro.launch.compile_cache import enable_compile_cache
    from repro.parallel.ctx import make_mesh
    from repro.serve.engine import VisionEngine
    from repro.vision.configs import get_vision_config
    from repro.vision.models import (collect_absmax, init_fp, quantize_net,
                                     vision_artifact_bytes)

    enable_compile_cache()
    mesh = None
    if args.mesh:
        try:
            dp, tp = (int(v) for v in args.mesh.split(","))
        except ValueError:
            raise SystemExit(f"--mesh {args.mesh!r}: expected DP,TP")
        need, have = dp * tp, len(jax.devices())
        if need > have:
            raise SystemExit(
                f"--mesh {args.mesh} needs {need} devices, found {have}; "
                "set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{need}")
        mesh = make_mesh((dp, tp), ("data", "model"),
                         devices=jax.devices()[:need])

    cfg = get_vision_config(args.net, smoke=args.smoke, a_bits=args.a_bits)
    candidates = tuple(int(b) for b in args.bits.split(","))
    rng = np.random.default_rng(args.seed)
    fp_params = init_fp(cfg, seed=args.seed)
    batches = [rng.uniform(0, 1, size=(
        args.calib_batch, *cfg.in_hw, cfg.in_ch)).astype(np.float32)
        for _ in range(args.calib_batches)]

    from repro.obs import trace as obs

    if args.from_plan:
        plan = load_plan(args.from_plan)
        absmax = collect_absmax(cfg, fp_params, batches)
        print(f"loaded plan {args.from_plan} ({len(plan.rules)} rules, "
              f"w_bits {plan.distinct_w_bits()})")
    else:
        plan, absmax = calibrate_and_plan(
            cfg, fp_params, batches, candidates=candidates,
            budget=args.budget, a_bits=args.a_bits, backend=args.backend,
            smoke=args.smoke)
        save_plan(plan, args.out)
        print(f"plan ({len(plan.rules)} rules, w_bits "
              f"{plan.distinct_w_bits()}) -> {args.out}")

    with obs.span("deploy.pack", cat="deploy", arch=cfg.name,
                  rules=len(plan.rules)):
        qnet = quantize_net(cfg, fp_params, absmax, plan=plan,
                            backend=args.backend)
    print(f"packed artifact: {vision_artifact_bytes(qnet):,} bytes, "
          f"per-layer bits {qnet.layer_bits()}")

    engine = VisionEngine(qnet, batch_size=args.batch, mesh=mesh,
                          backend=args.backend)
    if mesh is not None:
        print(f"mesh: data={mesh.shape['data']} model={mesh.shape['model']}"
              f" ({len(mesh.devices.flat)} devices)")
    dev = jax.devices()[0]
    print(f"kernel backends on {dev.platform}:{dev.device_kind}: "
          f"{engine.kernel_backends()}")
    images = rng.uniform(0, 1, size=(
        args.requests, *cfg.in_hw, cfg.in_ch)).astype(np.float32)
    with obs.span("serve.generate", cat="serve", requests=len(images),
                  batch=args.batch):
        logits = engine.run(images)
    preds = logits.argmax(-1)
    print(f"served {len(images)} images in waves of {args.batch}: "
          f"preds {preds.tolist()}")
    rep = engine.utilization_report()
    lat = rep["latency_us"]
    if lat is not None:
        qd = rep["queue_depth"]
        print(f"wave latency: p50={lat['p50'] / 1e3:.1f}ms "
              f"p95={lat['p95'] / 1e3:.1f}ms p99={lat['p99'] / 1e3:.1f}ms "
              f"over {lat['waves']} wave(s); queue depth mean "
              f"{qd['mean']:.1f} max {qd['max']}")
    if mesh is not None:
        print(f"utilization: mean {rep['mean_util']:.3f} over "
              f"{rep['waves']} waves, per-device "
              f"{[round(u, 3) for u in rep['per_device']]}")
    trace_path = obs.export_if_configured("vision_trace.json")
    if trace_path:
        print(f"trace -> {trace_path} (render: python -m repro.obs.report)")
    print("vision deploy done")


if __name__ == "__main__":
    main()
