"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and emit memory/cost/collective analysis JSON.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b \
        --shape train_4k --mesh pod            # 16x16 single pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multipod

This never allocates real arrays: inputs are ShapeDtypeStructs and only
.lower().compile() runs. Failures here are sharding/memory bugs by
definition (see EXPERIMENTS.md §Dry-run).

The os.environ lines below MUST run before any jax import (jax locks the
device count at first init); `repro.obs.env` is import-light (no jax) so
reading the knob through it is safe here.
"""
import os

from repro.obs import env as obsenv

os.environ["XLA_FLAGS"] = ((obsenv.get("REPRO_EXTRA_XLA") or "") +
                           " --xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, ShapeConfig, cells_for
from repro.launch.hlo_analysis import model_flops, roofline
from repro.launch.hlo_costs import analyze as analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models.api import build, get_config, list_archs
from repro.nn.module import param_count
from repro.train.step import (TrainStepConfig, make_decode_fns,
                              make_prefill_fns, make_train_fns)

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def active_params(model) -> float:
    """N_active for the 6ND rule: MoE counts top_k+shared experts only."""
    cfg = model.cfg
    shapes = jax.eval_shape(lambda k: model.init(k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    total = sum(s.size for s in jax.tree.leaves(shapes))
    if cfg.moe is None:
        return float(total)
    moe_leaves = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        keys = [getattr(k, "key", "") for k in path]
        if any(k in ("wi", "wg", "wo") for k in keys) and "moe" in keys and \
                "shared" not in keys:
            moe_leaves += leaf.size
    dense = total - moe_leaves
    frac = cfg.moe.top_k / cfg.moe.n_experts
    return float(dense + moe_leaves * frac)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             quant_mode: str = "off", save: bool = True,
             rules=None, tag: str = "") -> dict:
    cfg = get_config(arch)
    if quant_mode != "off":
        from repro.nn.layers import QuantConfig
        w_bits = int(quant_mode[1])
        a_bits = int(quant_mode[3]) if len(quant_mode) > 2 else 8
        cfg = dataclasses.replace(
            cfg, quant=QuantConfig(mode="int", w_bits=w_bits, a_bits=a_bits),
            kv_quant_bits=8 if shape_name.startswith(("decode", "long"))
            else 16)
    model = build(cfg)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    n_dev = mesh.devices.size

    t0 = time.time()
    kwargs = dict(rules=rules) if rules is not None else {}
    if shape.kind == "train":
        from repro.train.optimizer import OptConfig
        tcfg = TrainStepConfig()
        if cfg.param_dtype == "bfloat16":  # 100B+ archs: int8 m/v (DESIGN)
            tcfg = TrainStepConfig(opt=OptConfig(state_bits=8))
        init_fn, step, shards = make_train_fns(
            model, mesh, shape, tcfg, **kwargs)
        state_shapes = jax.eval_shape(
            init_fn, jax.ShapeDtypeStruct((2,), jnp.uint32))
        in_specs = model.input_specs(shape)
        jitted = jax.jit(step, in_shardings=(shards["state"],
                                             shards["batch"]),
                         out_shardings=(shards["state"], None),
                         donate_argnums=(0,))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(state_shapes, in_specs)
    elif shape.kind == "prefill":
        step, shards = make_prefill_fns(model, mesh, shape, **kwargs)
        pshapes = jax.eval_shape(lambda k: model.init(k),
                                 jax.ShapeDtypeStruct((2,), jnp.uint32))
        in_specs = model.input_specs(shape)
        jitted = jax.jit(step, in_shardings=(shards["params"],
                                             shards["batch"]))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(pshapes, in_specs)
    else:  # decode
        step, shards = make_decode_fns(model, mesh, shape, **kwargs)
        pshapes = jax.eval_shape(lambda k: model.init(k),
                                 jax.ShapeDtypeStruct((2,), jnp.uint32))
        in_specs = model.input_specs(shape)
        jitted = jax.jit(step, in_shardings=(
            shards["params"], shards["cache"], shards["token"],
            shards["index"]),
            out_shardings=(None, shards["cache"]), donate_argnums=(1,))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(pshapes, in_specs["cache"],
                                   in_specs["token"], in_specs["index"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    mc = analyze_hlo(hlo)  # trip-count aware: flops/io/collectives x loops

    flops_dev = mc.flops
    bytes_dev = mc.io_bytes
    terms = roofline(flops_dev, bytes_dev, mc.total_collective_in)

    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        # fwd-only: 2ND per token
    else:
        tokens = shape.global_batch  # one token per sequence
    n_act = active_params(model)
    mf_factor = 6.0 if shape.kind == "train" else 2.0
    mflops = mf_factor * n_act * tokens
    useful_ratio = mflops / max(flops_dev * n_dev, 1.0)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "devices": n_dev, "quant": quant_mode, "tag": tag,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "bytes_per_device": {
            "argument": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "total": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                      + mem.temp_size_in_bytes),
        },
        "flops_per_device": flops_dev,
        "hlo_bytes_per_device": bytes_dev,
        "collectives": {
            "counts": mc.collective_counts,
            "in_bytes": mc.collective_in,
            "out_bytes": mc.collective_out,
            "total_in": mc.total_collective_in,
        },
        "roofline": terms,
        "model_flops_total": mflops,
        "useful_flops_ratio": useful_ratio,
        "n_active_params": n_act,
    }
    if save:
        OUT_DIR.mkdir(parents=True, exist_ok=True)
        suffix = f"_{quant_mode}" if quant_mode != "off" else ""
        suffix += f"_{tag}" if tag else ""
        out = OUT_DIR / f"{arch}__{shape_name}__{mesh_kind}{suffix}.json"
        out.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--quant", default="off",
                    help="off | w8a8 | w4a8 | w4a4 | w2a8 | w2a2")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in list_archs():
            for s in cells_for(a):
                cells.append((a, s.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape in cells:
        try:
            rec = run_cell(arch, shape, args.mesh, args.quant, tag=args.tag)
            r = rec["roofline"]
            print(f"PASS {arch:26s} {shape:12s} {args.mesh:8s} "
                  f"mem/dev={rec['bytes_per_device']['total']/2**30:.2f}GiB "
                  f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s"
                  f" coll={r['collective_s']:.3e}s dom={r['dominant']}",
                  flush=True)
        except Exception as e:
            failures.append((arch, shape, repr(e)))
            print(f"FAIL {arch} {shape}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
