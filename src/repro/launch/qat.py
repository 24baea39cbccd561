"""QAT deployment launcher: train -> calibrate -> plan -> pack -> eval.

The accuracy-side analogue of `repro.launch.vision`: where that CLI
calibrates a random-init net and prices plans in bytes, this one closes
the full quantization-aware loop on labeled data — fake-quant train
(`repro.qat`), task-loss calibrate on the *trained* weights, search a
mixed-precision plan against the measured loss degradation, fold the
integer artifact, and report integer-path accuracy for uniform and
planned deployments side by side:

    PYTHONPATH=src python -m repro.launch.qat --smoke --steps 60 \
        --out qat_plan.json --report qat_accuracy.json

``--from-ckpt DIR`` resumes training from a `repro.ckpt` checkpoint
(the state `--ckpt-dir` saves every `ckpt_every` steps); ``--w-bits``
picks the uniform training width (the planned deployments always ride
on the same trained weights). The report JSON is a lightweight run
record (NOT the schema-validated BENCH_accuracy.json — that is
`benchmarks/accuracy`'s artifact; this one is per-run tooling).
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", default="qat-cnn",
                    help="vision config name (repro.vision.configs)")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke-size net (CI: <2 min CPU)")
    ap.add_argument("--dataset", default="synthetic",
                    choices=("synthetic", "mnist"))
    ap.add_argument("--data-dir", default=None,
                    help="IDX directory for --dataset mnist")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--w-bits", type=int, default=4,
                    help="uniform QAT width (0 = float training)")
    ap.add_argument("--a-bits", type=int, default=8)
    ap.add_argument("--learned-absmax", action="store_true",
                    help="PACT learned activation ranges instead of EMA")
    ap.add_argument("--bits", default="8,4,2",
                    help="plan candidate widths, widest first")
    ap.add_argument("--budget-frac", type=float, default=0.35)
    ap.add_argument("--calib-batches", type=int, default=4)
    ap.add_argument("--eval-batches", type=int, default=4)
    ap.add_argument("--eval-batch", type=int, default=100)
    ap.add_argument("--backend", default=None)
    ap.add_argument("--mesh", default=None, metavar="DP",
                    help="shard training batches over DP devices")
    ap.add_argument("--ckpt-dir", default=None,
                    help="save full train state here (repro.ckpt)")
    ap.add_argument("--from-ckpt", default=None,
                    help="resume training from this checkpoint dir")
    ap.add_argument("--out", default="qat_plan.json",
                    help="plan artifact (deploy.policy schema)")
    ap.add_argument("--report", default="qat_accuracy.json",
                    help="accuracy run record")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    # heavy imports after argparse so --help stays instant
    import json

    import jax
    import numpy as np

    from repro.deploy.calibrate import calibrate_vision
    from repro.deploy.planner import auto_budget, plan_mixed_precision
    from repro.deploy.policy import save_plan
    from repro.obs import trace as obs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.parallel.ctx import make_mesh
    from repro.qat.data import make_dataset
    from repro.qat.evaluate import deploy, evaluate_int, fold_check
    from repro.qat.train import QATConfig, train_qat
    from repro.vision.configs import get_vision_config
    from repro.vision.models import streamed_weight_bytes

    cfg = get_vision_config(args.net, smoke=args.smoke, a_bits=args.a_bits)
    data = make_dataset(args.dataset, split="train", seed=args.seed,
                        data_dir=args.data_dir)
    test = make_dataset(args.dataset, split="test", seed=args.seed,
                        data_dir=args.data_dir)
    candidates = tuple(int(b) for b in args.bits.split(","))

    enable_compile_cache()
    mesh = None
    if args.mesh:
        dp = int(args.mesh.split(",")[0])
        mesh = make_mesh((dp,), ("data",), devices=jax.devices()[:dp])

    qc = QATConfig(steps=args.steps, batch=args.batch, lr=args.lr,
                   warmup=args.warmup,
                   w_bits=(args.w_bits or None), a_bits=args.a_bits,
                   learned_absmax=args.learned_absmax, seed=args.seed,
                   log_every=max(args.steps // 5, 1))
    with obs.span("launch.qat", cat="qat", net=cfg.name,
                  steps=args.steps, w_bits=args.w_bits):
        result = train_qat(cfg, data, qc, mesh=mesh,
                           ckpt_dir=args.ckpt_dir,
                           from_ckpt=args.from_ckpt)
        print(f"# trained {cfg.name}: "
              + " ".join(f"step{r['step']}={r['loss']:.3f}"
                         for r in result.log))
        if args.w_bits:
            fold_check(result)
            print("# fold_check: weight grids fold bit-exact")

        # task-loss calibration on the trained weights
        xs, ys = [], []
        for x, y in data.batches(args.batch, args.calib_batches):
            xs.append(np.asarray(x))
            ys.append(np.asarray(y))
        stats, _ = calibrate_vision(cfg, result.model_params(), xs,
                                    sensitivity="task_loss", labels=ys,
                                    a_bits=args.a_bits, bits=candidates)
        budget = auto_budget(stats, candidates, frac=args.budget_frac)
        plan = plan_mixed_precision(
            stats, budget, candidates=candidates, a_bits=args.a_bits,
            backend=args.backend,
            meta={"source": "task_loss", "net": cfg.name},
            granularity="channel_group")
        print(f"# plan (budget={budget:.4f}): "
              f"{ {r.pattern: r.w_bits for r in plan.rules} }")
        save_plan(plan, args.out)
        print(f"# wrote plan -> {args.out}")

        rows = []
        deployments = [("uniform", None)] if not args.w_bits else \
            [(f"uniform_w{args.w_bits}", None)]
        deployments.append(("task_loss_plan", plan))
        for tag, p in deployments:
            qnet = deploy(result, plan=p, backend=args.backend)
            ev = evaluate_int(qnet,
                              test.batches(args.eval_batch,
                                           args.eval_batches),
                              backend=args.backend)
            row = {"deployment": tag,
                   "accuracy": round(float(ev["accuracy"]), 6),
                   "correct": int(ev["correct"]), "n": int(ev["n"]),
                   "packed_weight_bytes":
                       int(streamed_weight_bytes(qnet))}
            rows.append(row)
            print(f"# {tag}: acc={row['accuracy']:.4f} "
                  f"bytes={row['packed_weight_bytes']}")

    report = {"net": cfg.name, "dataset": args.dataset,
              "train": {"steps": args.steps, "w_bits": args.w_bits,
                        "a_bits": args.a_bits, "seed": args.seed,
                        "final_loss": result.log[-1]["loss"]},
              "budget": budget, "rows": rows}
    with open(args.report, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote report -> {args.report}")


if __name__ == "__main__":
    main()
