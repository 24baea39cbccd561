"""Serving launcher: load (or init) params, optionally convert to the
packed sub-byte deployment artifact, and serve a batch of synthetic
requests through the engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
        --quant w4a8 --requests 8 --max-new 16

Mixed-precision serving: pass a deployment plan produced by
`python -m repro.launch.deploy` and each dense layer is packed at its
plan-resolved bit-width instead of one uniform --quant:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
        --plan plan.json --requests 8

Cluster-parallel serving: ``--mesh dp,tp`` builds a (data=dp, model=tp)
device mesh (the paper's N-core cluster; on CPU force host devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``), shards request
waves data-parallel over the `data` axis, and prints the per-device slot
utilization report after serving:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
        --quant w4a8 --requests 8 --batch 4 --mesh 4,2
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.convert import convert_params
from repro.models.api import build, get_config
from repro.nn.layers import QuantConfig
from repro.obs import trace as obs
from repro.parallel.ctx import make_mesh
from repro.serve.engine import Engine, Request


def build_serving(cfg, *, quant: str = "off", plan_path=None, ckpt=None,
                  seed: int = 0):
    """Build the served model and its parameter tree.

    The float tree is initialised (or restored) and quantized on the host
    CPU, where a full-width float32 checkpoint fits; only the tree that is
    served (packed when ``quant``/``plan_path`` ask for it) is put on the
    default device. Returns ``(model, params, plan, mode)``.
    """
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        fp_model = build(cfg)
        if ckpt:
            from repro.ckpt.checkpoint import restore
            state, _ = restore(ckpt)
            fp_params = state["params"] if "params" in state else state
        else:
            fp_params = fp_model.init(jax.random.PRNGKey(seed))

        plan = None
        if plan_path:
            from repro.deploy.apply import apply_plan
            from repro.deploy.policy import load_plan
            plan = load_plan(plan_path)
            qcfg = QuantConfig(mode="int", w_bits=plan.default_w_bits,
                               a_bits=plan.default_a_bits)
            model = build(dataclasses.replace(cfg, quant=qcfg,
                                              quant_plan=plan))
            params = apply_plan(model.init(jax.random.PRNGKey(0)),
                                fp_params, plan, plan.default_w_bits)
            mode = f"plan:{plan_path} w_bits={plan.distinct_w_bits()}"
        elif quant != "off":
            qcfg = QuantConfig(mode="int", w_bits=int(quant[1]),
                               a_bits=int(quant[3]))
            model = build(dataclasses.replace(cfg, quant=qcfg))
            params = convert_params(model.init(jax.random.PRNGKey(0)),
                                    fp_params, qcfg.w_bits)
            mode = quant
        else:
            model, params = fp_model, fp_params
            mode = "off"
    return model, jax.device_put(params, jax.devices()[0]), plan, mode


def make_requests(cfg, n: int, max_new: int, seed: int = 0):
    """``n`` synthetic requests: prompts of 2-7 random tokens."""
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(2, cfg.vocab, size=(
        int(rng.integers(2, 8)),)).astype(np.int32),
        max_new_tokens=max_new) for _ in range(n)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quant", default="off", help="off | w8a8 | w4a8 ...")
    ap.add_argument("--plan", default=None,
                    help="mixed-precision plan JSON (repro.launch.deploy); "
                         "overrides --quant")
    ap.add_argument("--kv-bits", type=int, default=16, choices=[16, 8])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir to load params from")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="serve on a (data=DP, model=TP) device mesh, "
                         "e.g. --mesh 4,2; waves are sharded "
                         "data-parallel over DP (batch must divide DP). "
                         "On CPU, export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first")
    args = ap.parse_args()
    enable_compile_cache()

    mesh = None
    if args.mesh:
        try:
            dp, tp = (int(v) for v in args.mesh.split(","))
        except ValueError:
            raise SystemExit(
                f"--mesh {args.mesh!r}: expected DP,TP (two comma-"
                "separated ints), e.g. --mesh 4,2 or --mesh 8,1")
        need = dp * tp
        have = len(jax.devices())
        if have < need:
            raise SystemExit(
                f"--mesh {args.mesh} needs {need} devices, found {have}; "
                "on CPU set XLA_FLAGS=--xla_force_host_platform_"
                f"device_count={need} before launching")
        mesh = make_mesh((dp, tp), ("data", "model"),
                         devices=jax.devices()[:need])

    if args.smoke:
        from repro.models.api import get_smoke_config
        cfg = get_smoke_config(args.arch)
    else:
        cfg = get_config(args.arch)
    cfg = dataclasses.replace(cfg, kv_quant_bits=args.kv_bits)
    model, params, plan, mode = build_serving(
        cfg, quant=args.quant, plan_path=args.plan, ckpt=args.ckpt,
        seed=args.seed)

    from repro.nn.module import param_bytes
    pbytes = param_bytes(params)
    print(f"{cfg.name} [{mode}] params {pbytes / 2**20:.1f} MiB "
          f"({pbytes:,} bytes)")

    reqs = make_requests(cfg, args.requests, args.max_new, args.seed)
    eng = Engine(model, params, batch_size=args.batch, max_len=args.max_len,
                 plan=plan, mesh=mesh)
    if mesh is not None:
        print(f"mesh: data={mesh.shape['data']} model={mesh.shape['model']} "
              f"({len(mesh.devices.flat)} devices; waves sharded over "
              "'data')")
    if mode != "off":
        from repro.kernels.api import ENV_VAR
        kb = eng.kernel_backends()
        print(f"kernel backends: qdot={kb['qdot']} qconv={kb['qconv']} "
              f"(override: {ENV_VAR} or QuantConfig.backend)")
    t0 = time.time()
    with obs.span("serve.generate", cat="serve", requests=len(reqs),
                  batch=args.batch):
        out = eng.generate(reqs)
    dt = time.time() - t0
    toks = sum(len(r.out) for r in out)
    dev = jax.devices()[0]
    print(f"{toks} tokens / {dt:.2f}s = {toks / dt:.1f} tok/s on "
          f"{dev.platform}:{dev.device_kind} (includes compilation)")
    rep = eng.utilization_report()
    lat = rep["latency_us"]
    if lat is not None:
        qd = rep["queue_depth"]
        print(f"wave latency: p50={lat['p50'] / 1e3:.1f}ms "
              f"p95={lat['p95'] / 1e3:.1f}ms p99={lat['p99'] / 1e3:.1f}ms "
              f"over {lat['waves']} wave(s); queue depth mean "
              f"{qd['mean']:.1f} max {qd['max']}")
    if mesh is not None:
        per = " ".join(f"d{d}={u:.0%}" for d, u in
                       enumerate(rep["per_device"]))
        print(f"cluster utilization: {rep['mean_util']:.0%} over "
              f"{rep['waves']} wave(s) [{per}] — idle devices == padded "
              "slots")
    for r in out[:3]:
        print("  prompt", r.prompt.tolist(), "->", r.out.tolist())
    trace_path = obs.export_if_configured("serve_trace.json")
    if trace_path:
        print(f"trace -> {trace_path} (render: python -m repro.obs.report)")


if __name__ == "__main__":
    main()
