"""Training launcher: config-driven, fault-tolerant, mesh-aware.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b \
        --steps 200 --batch 8 --seq 256 --mesh host --ckpt /tmp/ckpt

`--mesh host` uses whatever devices exist (CPU tests / single host);
`--mesh pod|multipod` builds the production mesh (requires the matching
device count — on a real slice, run under the usual multi-host launcher).
Checkpoints are atomic + async; re-running the same command resumes.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs.base import SHAPES, ShapeConfig
from repro.data.pipeline import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.api import build, get_config
from repro.nn.layers import QuantConfig
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.train.optimizer import OptConfig
from repro.train.step import TrainStepConfig, make_train_fns


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config for this arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=100)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "pod", "multipod"])
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--qat", default=None,
                    help="fake-quant bits for QAT, e.g. w4a8")
    ap.add_argument("--opt-state-bits", type=int, default=32,
                    choices=[32, 8])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        from repro.models.api import get_smoke_config
        cfg = get_smoke_config(args.arch)
    else:
        cfg = get_config(args.arch)
    if args.qat:
        cfg = dataclasses.replace(cfg, quant=QuantConfig(
            mode="fake", w_bits=int(args.qat[1]), a_bits=int(args.qat[3])))

    model = build(cfg)
    mesh = (make_host_mesh() if args.mesh == "host" else
            make_production_mesh(multi_pod=args.mesh == "multipod"))
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    tcfg = TrainStepConfig(opt=OptConfig(
        lr=args.lr, warmup=args.warmup, total_steps=args.steps,
        state_bits=args.opt_state_bits))
    init_fn, step, shards = make_train_fns(model, mesh, shape, tcfg)
    data = SyntheticLM(
        cfg.vocab, args.batch, args.seq, seed=args.seed,
        src_dim=cfg.d_model if (cfg.family == "encdec" or cfg.cross_every)
        else 0,
        src_len=args.seq if cfg.family == "encdec" else cfg.src_len)

    with jax.set_mesh(mesh):
        jstep = jax.jit(step, in_shardings=(shards["state"],
                                            shards["batch"]),
                        out_shardings=(shards["state"], None),
                        donate_argnums=(0,))
        trainer = Trainer(init_fn, jstep, data, TrainerConfig(
            total_steps=args.steps, ckpt_every=args.ckpt_every,
            ckpt_dir=args.ckpt))
        state, log = trainer.run(jax.random.PRNGKey(args.seed))
    for rec in log[:: max(len(log) // 10, 1)]:
        print(f"step {rec['step']:6d} loss {rec['loss']:.4f} "
              f"gnorm {rec['grad_norm']:.2f} {rec['dt'] * 1e3:.0f} ms")
    print(f"final step {log[-1]['step']} loss {log[-1]['loss']:.4f}; "
          f"stragglers {trainer.monitor.flags}; ckpts at {args.ckpt}")


if __name__ == "__main__":
    main()
