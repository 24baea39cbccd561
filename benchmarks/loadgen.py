"""Open-loop serving load generator (BENCH_serving.json).

Drives the continuous-batching runtime (`repro.serve.runtime`) and the
legacy synchronous-wave policy with the *same* seeded open-loop workload
— Poisson arrivals at a configured offered QPS, mixed prompt/generation
lengths — and reports per-policy p50/p95/p99 request latency, request
and token throughput, queue depth, and slot occupancy. The comparison is
the PR's acceptance artifact: continuous batching must beat the wave
baseline on throughput *and* tail latency at the same offered load,
because a freed slot is re-admitted at the next step instead of idling
behind the wave's straggler (the paper's idle-core argument at request
granularity).

Time is **virtual**: one engine step costs ``--step-cost`` seconds and
arrivals are pre-drawn from the seed, so the whole simulation — arrival
times, admission order, per-request latencies, every derived stat — is
bit-reproducible run over run (CI asserts replay determinism). Wall
time on CPU would only measure XLA jitter; the queueing behaviour under
load is what the benchmark isolates. Per-request *outputs* are identical
across policies by the runtime's bit-exactness invariant, so the two
rows differ only in scheduling.

    PYTHONPATH=src python -m benchmarks.loadgen --json BENCH_serving.json
"""
import argparse
import collections
import json
import os
import sys

# must precede the first jax import to materialize host-platform devices
if "jax" not in sys.modules and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np

POLICIES = ("wave", "continuous")


def build_workload(cfg, args):
    """Seeded open-loop workload: (arrival_time, prompt, max_new) rows.

    Inter-arrival gaps are Exp(1/qps) (Poisson process); prompt lengths
    and generation budgets are uniform over the configured ranges — the
    mixed-length mix that makes synchronous waves straggle."""
    rng = np.random.default_rng(args.seed)
    gaps = rng.exponential(1.0 / args.qps, size=args.requests)
    arrivals = np.cumsum(gaps)
    rows = []
    for t in arrivals:
        plen = int(rng.integers(args.prompt_min, args.prompt_max + 1))
        mnew = int(rng.integers(args.new_min, args.new_max + 1))
        prompt = rng.integers(2, cfg.vocab, size=(plen,)).astype(np.int32)
        rows.append((float(t), prompt, mnew))
    return rows


def run_policy(policy, model, params, workload, args, mesh=None):
    """Simulate one policy over the workload on a virtual clock."""
    from repro.serve.runtime import LMDecodeAdapter, Request, Scheduler

    adapter = LMDecodeAdapter(model, params, max_len=args.max_len,
                              mesh=mesh)
    sched = Scheduler(adapter, args.slots, mesh=mesh, policy=policy)
    pending = collections.deque(
        (t, Request(prompt=p, max_new_tokens=m)) for t, p, m in workload)
    now, t0 = 0.0, pending[0][0]
    while pending or not sched.idle:
        while pending and pending[0][0] <= now:
            t, req = pending.popleft()
            sched.submit(req, now=t)     # latency includes queueing delay
        if sched.idle and pending:       # idle gap: jump to next arrival
            now = pending[0][0]
            continue
        sched.step(now=now)
        now += args.step_cost
    rep = sched.serving_report()
    makespan = max(r["finish_t"] for r in sched.request_log) - t0
    return {
        "policy": policy,
        "requests": rep["requests"],
        "steps": rep["steps"],
        "tokens_out": rep["tokens_out"],
        "makespan_s": round(makespan, 6),
        "throughput_rps": round(rep["requests"] / makespan, 6),
        "throughput_tps": round(rep["tokens_out"] / makespan, 6),
        "latency_s": {k: round(v, 6) for k, v in rep["latency"].items()},
        "queue_depth": rep["queue_depth"],
        "occupancy": {k: round(v, 6) for k, v in rep["occupancy"].items()},
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--qps", type=float, default=0.6,
                    help="offered load, arrivals per virtual second")
    ap.add_argument("--step-cost", type=float, default=1.0,
                    help="virtual seconds per engine step")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=32)
    ap.add_argument("--prompt-min", type=int, default=2)
    ap.add_argument("--prompt-max", type=int, default=6)
    ap.add_argument("--new-min", type=int, default=1)
    ap.add_argument("--new-max", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", action="store_true",
                    help="shard slots data-parallel over a (dp, tp) mesh")
    ap.add_argument("--json", default=None, help="write BENCH_serving.json")
    args = ap.parse_args(argv)

    import jax
    from repro.configs.qwen2p5_3b import smoke_config
    from repro.models.api import build
    from repro.parallel.ctx import make_mesh

    cfg = smoke_config()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = None
    if args.mesh:
        dp = min(4, len(jax.devices()))
        tp = len(jax.devices()) // dp
        mesh = make_mesh((dp, tp), ("data", "model"),
                         devices=jax.devices()[: dp * tp])

    workload = build_workload(cfg, args)
    print(f"workload: {args.requests} requests, qps={args.qps}, "
          f"prompts [{args.prompt_min},{args.prompt_max}], max_new "
          f"[{args.new_min},{args.new_max}], slots={args.slots}, "
          f"seed={args.seed}" + (f", dp={mesh.shape['data']}" if mesh
                                 else ""))
    rows = []
    for policy in POLICIES:
        row = run_policy(policy, model, params, workload, args,
                         mesh=mesh)
        rows.append(row)
        lat = row["latency_s"]
        print(f"{policy:>10}: {row['throughput_rps']:.3f} req/s "
              f"{row['throughput_tps']:.3f} tok/s over {row['steps']} "
              f"steps; latency p50={lat['p50']:.1f}s p99={lat['p99']:.1f}s"
              f"; occupancy {row['occupancy']['mean']:.0%}")

    wave = next(r for r in rows if r["policy"] == "wave")
    cont = next(r for r in rows if r["policy"] == "continuous")
    payload = {
        "version": 1,
        "workload": {
            "model": cfg.name, "requests": args.requests,
            "qps": args.qps, "step_cost_s": args.step_cost,
            "slots": args.slots, "max_len": args.max_len,
            "prompt_lens": [args.prompt_min, args.prompt_max],
            "max_new": [args.new_min, args.new_max],
            "seed": args.seed,
            "devices": (1 if mesh is None
                        else int(mesh.shape["data"])),
        },
        "rows": rows,
        "acceptance": {
            "throughput_gain": round(
                cont["throughput_tps"] / wave["throughput_tps"], 4),
            "p99_ratio": round(
                cont["latency_s"]["p99"] / wave["latency_s"]["p99"], 4),
        },
    }
    gain, p99 = (payload["acceptance"]["throughput_gain"],
                 payload["acceptance"]["p99_ratio"])
    print(f"continuous vs wave: {gain:.2f}x throughput, "
          f"{p99:.2f}x p99 latency")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {args.json}")
    return payload


if __name__ == "__main__":
    main()
