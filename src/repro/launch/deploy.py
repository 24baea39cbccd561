"""Mixed-precision deployment launcher: calibrate -> plan -> pack -> save.

Turns an fp checkpoint (or a fresh init in --smoke runs) into a per-layer
W{8,4,2} packed serving artifact plus the JSON plan that describes it:

    PYTHONPATH=src python -m repro.launch.deploy --arch qwen2.5-3b --smoke \
        --budget auto --out plan.json

The plan is then served with `python -m repro.launch.serve ... --plan
plan.json` (see README §Mixed-precision deployment).

``--from-plan old_plan.json`` skips calibration/search and re-packs from
an existing plan, re-saving it to ``--out`` in the current schema — the
upgrade path for pre-registry (schema-v1 ``use_kernel``) artifacts, which
load with a DeprecationWarning and map onto the ``backend`` field.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.deploy.apply import apply_plan
from repro.deploy.calibrate import calibrate
from repro.deploy.planner import auto_budget, plan_mixed_precision
from repro.deploy.policy import PLAN_VERSION, load_plan, save_plan
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.convert import artifact_bytes
from repro.models.api import Model, build, get_config
from repro.nn.layers import QuantConfig
from repro.obs import trace as obs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--budget", default="auto",
                    help="total sensitivity budget (float) or 'auto'")
    ap.add_argument("--bits", default="8,4,2",
                    help="candidate w_bits, widest first")
    ap.add_argument("--a-bits", type=int, default=8)
    ap.add_argument("--backend", default=None,
                    help="kernel backend the plan rules route through "
                         "(repro.kernels.api; default: registry resolution)")
    ap.add_argument("--from-plan", default=None,
                    help="existing plan JSON: skip calibrate/search, "
                         "re-save to --out in the current schema, and pack")
    ap.add_argument("--calib-batches", type=int, default=2)
    ap.add_argument("--calib-batch", type=int, default=2)
    ap.add_argument("--calib-seq", type=int, default=32)
    ap.add_argument("--out", default="plan.json")
    ap.add_argument("--artifact", default=None,
                    help="directory to save the packed param tree into")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir to load fp params from")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        from repro.models.api import get_smoke_config
        cfg = get_smoke_config(args.arch)
    else:
        cfg = get_config(args.arch)
    candidates = tuple(int(b) for b in args.bits.split(","))

    fp_model = build(cfg)
    if args.ckpt:
        from repro.ckpt.checkpoint import restore
        state, _ = restore(args.ckpt)
        fp_params = state["params"] if "params" in state else state
    else:
        fp_params = fp_model.init(jax.random.PRNGKey(args.seed))

    if args.from_plan:
        ignored = [f for f, dflt in (("--backend", None), ("--bits", "8,4,2"),
                                     ("--budget", "auto"), ("--a-bits", 8))
                   if getattr(args, f.lstrip("-").replace("-", "_")) != dflt]
        if ignored:
            print(f"warning: {', '.join(ignored)} ignored with --from-plan "
                  "(the existing plan's rules are kept verbatim)")
        plan = load_plan(args.from_plan)   # v1 artifacts warn + map backend
        save_plan(plan, args.out)
        print(f"re-saved plan {args.from_plan} -> {args.out} "
              f"(schema v{PLAN_VERSION}, {len(plan.rules)} rules, "
              f"w_bits {plan.distinct_w_bits()}, backends "
              f"{sorted({r.backend for r in plan.rules}, key=str)})")
    else:
        rng = np.random.default_rng(args.seed)
        batches = [rng.integers(2, cfg.vocab, size=(
            args.calib_batch, args.calib_seq)).astype(np.int32)
            for _ in range(args.calib_batches)]
        print(f"calibrating {cfg.name}: {len(batches)} batches of "
              f"{args.calib_batch}x{args.calib_seq} tokens, "
              f"candidates W{candidates}")
        with obs.span("deploy.calibrate", cat="deploy", arch=cfg.name,
                      batches=len(batches), candidates=candidates):
            stats = calibrate(fp_model, fp_params, batches, bits=candidates,
                              a_bits=args.a_bits)

        with obs.span("deploy.plan", cat="deploy", arch=cfg.name,
                      paths=len(stats)):
            budget = (auto_budget(stats, candidates)
                      if args.budget == "auto" else float(args.budget))
            plan = plan_mixed_precision(
                stats, budget, candidates=candidates, a_bits=args.a_bits,
                backend=args.backend,
                meta={"arch": cfg.name, "smoke": args.smoke})
        print(f"budget {budget:.6g} -> total sensitivity "
              f"{plan.meta['total_sensitivity']:.6g}")
        for r in plan.rules:
            st = stats[r.pattern]
            print(f"  {r.pattern:<28} W{r.w_bits}A{r.a_bits}  "
                  f"absmax={st.a_absmax:.3f}  "
                  f"sens={{{', '.join(f'{b}:{st.sens(b):.2e}' for b in candidates)}}}")
        save_plan(plan, args.out)
        print(f"plan ({len(plan.rules)} rules, w_bits "
              f"{plan.distinct_w_bits()}) -> {args.out}")

    base = QuantConfig(mode="int", w_bits=plan.default_w_bits,
                       a_bits=plan.default_a_bits)
    q_model = Model(dataclasses.replace(cfg, quant=base, quant_plan=plan))
    with obs.span("deploy.pack", cat="deploy", arch=cfg.name,
                  rules=len(plan.rules)):
        q_params = apply_plan(q_model.init(jax.random.PRNGKey(0)),
                              fp_params, plan, plan.default_w_bits)
    mixed_b = artifact_bytes(q_params)
    fp_b = artifact_bytes(fp_params)
    if {"packed_weight_bytes", "uniform_w8_bytes"} <= set(plan.meta):
        # uniform-w8 comparison without packing a second artifact: the
        # non-dense remainder (embeds/norms/biases) is identical, only the
        # planner-accounted dense bytes differ
        w8_b = (mixed_b - plan.meta["packed_weight_bytes"]
                + plan.meta["uniform_w8_bytes"])
        print(f"artifact bytes: fp {fp_b:,}  uniform-w8 {w8_b:,}  "
              f"mixed {mixed_b:,}  ({mixed_b / w8_b:.3f}x of w8)")
    else:  # hand-written / stripped-meta plans (--from-plan)
        print(f"artifact bytes: fp {fp_b:,}  mixed {mixed_b:,}")

    if args.artifact:
        from repro.ckpt.checkpoint import save
        save(args.artifact, 0, {"params": q_params})
        save_plan(plan, f"{args.artifact}/plan.json")
        print(f"packed artifact -> {args.artifact}")
    trace_path = obs.export_if_configured("deploy_trace.json")
    if trace_path:
        print(f"trace -> {trace_path} (render: python -m repro.obs.report)")
    print("deploy done")


if __name__ == "__main__":
    main()
