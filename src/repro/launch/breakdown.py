"""Per-op IO/collective breakdown for one dry-run cell — the 'profile'
driving §Perf hypotheses (dry-run counterpart of a wall-clock profiler).

    PYTHONPATH=src python -m repro.launch.breakdown --arch X --shape Y
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES
from repro.launch.hlo_costs import analyze
from repro.launch.mesh import make_production_mesh
from repro.models.api import build, get_config
from repro.train.step import (TrainStepConfig, make_decode_fns,
                              make_prefill_fns, make_train_fns)


def compile_cell(arch, shape_name, mesh_kind="pod", quant="off", rules=None):
    import dataclasses
    cfg = get_config(arch)
    if quant != "off":
        from repro.nn.layers import QuantConfig
        cfg = dataclasses.replace(
            cfg, quant=QuantConfig(mode="int", w_bits=int(quant[1]),
                                   a_bits=int(quant[3])),
            kv_quant_bits=8 if shape_name.startswith(("decode", "long"))
            else 16)
    model = build(cfg)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    kwargs = dict(rules=rules) if rules is not None else {}
    if shape.kind == "train":
        from repro.train.optimizer import OptConfig
        tcfg = TrainStepConfig()
        if cfg.param_dtype == "bfloat16":
            tcfg = TrainStepConfig(opt=OptConfig(state_bits=8))
        init_fn, step, shards = make_train_fns(model, mesh, shape, tcfg,
                                               **kwargs)
        ss = jax.eval_shape(init_fn, jax.ShapeDtypeStruct((2,), jnp.uint32))
        ins = model.input_specs(shape)
        with jax.set_mesh(mesh):
            return jax.jit(step, in_shardings=(shards["state"],
                                               shards["batch"]),
                           out_shardings=(shards["state"], None),
                           donate_argnums=(0,)).lower(ss, ins).compile()
    if shape.kind == "prefill":
        step, shards = make_prefill_fns(model, mesh, shape, **kwargs)
        ps = jax.eval_shape(lambda k: model.init(k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
        ins = model.input_specs(shape)
        with jax.set_mesh(mesh):
            return jax.jit(step, in_shardings=(shards["params"],
                                               shards["batch"])
                           ).lower(ps, ins).compile()
    step, shards = make_decode_fns(model, mesh, shape, **kwargs)
    ps = jax.eval_shape(lambda k: model.init(k),
                        jax.ShapeDtypeStruct((2,), jnp.uint32))
    ins = model.input_specs(shape)
    with jax.set_mesh(mesh):
        return jax.jit(step, in_shardings=(
            shards["params"], shards["cache"], shards["token"],
            shards["index"]),
            out_shardings=(None, shards["cache"]),
            donate_argnums=(1,)).lower(
                ps, ins["cache"], ins["token"], ins["index"]).compile()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="pod")
    ap.add_argument("--quant", default="off")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args()
    c = compile_cell(args.arch, args.shape, args.mesh, args.quant)
    mc = analyze(c.as_text(), breakdown=True)
    print(f"flops/dev {mc.flops:.3e}  io {mc.io_bytes/1e9:.1f} GB/dev  "
          f"coll_in {mc.total_collective_in/1e9:.1f} GB/dev")
    print("collectives:", {k: f"{v/1e9:.1f}GB"
                           for k, v in mc.collective_in.items() if v})
    print(f"{'GB':>8} {'xTrip':>6} op/name")
    for t, m, cn, op, n, osh in mc.breakdown[: args.top]:
        print(f"{t/1e9:8.1f} x{m:5.0f} {op:14s} {n:44s} {osh}")


if __name__ == "__main__":
    main()
