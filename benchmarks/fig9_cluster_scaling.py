"""Fig. 9 analogue — cluster scaling of the packed GEMM on a real mesh.

Paper: MAC/cycle of the 8-core PULP cluster vs single core — near-linear
1->8 speedup because each core MACs a disjoint output-channel group with
operands resident (no inter-core reduction). TPU adaptation: the **same
quantized GEMM artifact** runs through `repro.kernels.api.qdot_sharded`
on a 1..8-device mesh (one JAX device ↔ one cluster core): packed weights
tensor-parallel over the output-feature axis, int32 accumulation local
per shard, psum-free epilogue — then wall-clock per mesh size plus the
analytic per-device roofline are emitted. On CPU the devices are
host-platform slices (``--xla_force_host_platform_device_count``), so
measured wall-clock is structure-comparative; the per-device flop/byte
column carries the paper's scaling argument either way. Results are
asserted bit-exact against the single-device reference before timing.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m benchmarks.fig9_cluster_scaling \
        --devices 1,2,4,8 --json BENCH_cluster.json
"""
import argparse
import json
import os
import sys

# must precede the first jax import to materialize host-platform devices;
# a no-op when jax is already loaded (e.g. under benchmarks.run)
if "jax" not in sys.modules and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call, PEAK_FLOPS, HBM_BW
from repro.core import packing
from repro.core.quantize import QuantizedLinearParams
from repro.kernels import api
from repro.parallel.ctx import make_mesh
from repro.parallel.sharding import shard_packed_linear

M, K, N = 256, 4608, 512


def _artifact(bits, rng):
    """One packed GEMM deployment artifact + activation batch at `bits`."""
    lo, hi = packing.int_range(bits, True)
    w = rng.integers(lo, hi + 1, size=(K, N)).astype(np.int8)
    wp = packing.pack(jnp.asarray(w), bits, axis=0)
    params = QuantizedLinearParams(
        w_packed=wp, w_bits=bits, a_bits=bits, a_signed=False,
        kappa=jnp.asarray(rng.integers(-64, 64, (N,)).astype(np.int32)),
        lam=jnp.asarray(rng.integers(-2**12, 2**12, (N,)).astype(np.int32)),
        m=jnp.asarray(rng.integers(0, 2**15, (N,)).astype(np.int32)),
        d=18, out_bits=8, k_logical=K)
    alo, ahi = packing.int_range(bits, False)
    x = jnp.asarray(rng.integers(alo, ahi + 1, (M, K)).astype(np.int8))
    return params, x


def main(devices=None, json_path="BENCH_cluster.json", backend=None,
         bits_sweep=(8, 4, 2)):
    avail = len(jax.devices())
    if devices is None:
        devices = [d for d in (1, 2, 4, 8) if d <= avail]
    rng = np.random.default_rng(0)
    rows = []
    for bits in bits_sweep:
        params, x = _artifact(bits, rng)
        ref = np.asarray(api.qdot(params, x, backend=backend))
        measured = []
        for n_dev in devices:
            if n_dev > avail:
                print(f"# fig9: skipping {n_dev} devices "
                      f"(only {avail} available; set XLA_FLAGS="
                      f"--xla_force_host_platform_device_count={n_dev})")
                continue
            mesh = make_mesh((1, n_dev), ("data", "model"),
                             devices=jax.devices()[:n_dev])
            sharded = shard_packed_linear(params, mesh)
            # jit so timing measures the compiled sharded GEMM, not
            # per-call shard_map retracing
            fn = jax.jit(lambda xx: api.qdot(sharded, xx, mesh=mesh,
                                             backend=backend))
            assert np.array_equal(np.asarray(fn(x)), ref), \
                f"sharded result diverged at {bits}-bit x {n_dev} devices"
            measured.append((n_dev, time_call(fn, x)))
        if not measured:
            continue
        # speedup is vs the smallest measured cluster (ideally 1 device),
        # regardless of --devices ordering or skipped sizes
        base_us = min(measured)[1]
        for n_dev, us in measured:
            speedup = base_us / us if us > 0 else float("nan")
            # per-device roofline terms: weights + epilogue vectors are
            # TP-sharded (1/n), activations replicated, no collective
            flops = 2 * M * K * N / n_dev
            w_bytes = K * N * bits // 8 // n_dev
            x_bytes = M * K * bits // 8
            t_proj = max(flops / PEAK_FLOPS, (w_bytes + x_bytes) / HBM_BW)
            rows.append({
                "name": f"fig9_{bits}bit_dev{n_dev}", "bits": bits,
                "devices": n_dev, "us_per_call": round(float(us), 1),
                "speedup": round(float(speedup), 3),
                "efficiency": round(float(speedup) / n_dev, 3),
                "per_dev_flops": flops, "coll_bytes": 0,
                "proj_us_v5e": round(t_proj * 1e6, 3)})
            emit(f"fig9_{bits}bit_dev{n_dev}", us,
                 f"speedup={speedup:.2f};per_dev_flops={flops:.2e};"
                 f"coll_bytes=0;proj_us_v5e={t_proj * 1e6:.3f}",
                 backend or "default")
    if json_path and rows:
        payload = {"version": 1, "gemm": {"M": M, "K": K, "N": N},
                   "path": "repro.kernels.api.qdot_sharded",
                   "rows": rows}
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(rows)} rows -> {json_path}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma-separated mesh sizes to sweep")
    ap.add_argument("--json", default="BENCH_cluster.json",
                    help="output path for the JSON rows ('' disables)")
    ap.add_argument("--backend", default=None,
                    help="force a kernel backend (default: registry "
                         "resolution per local shard shape)")
    ap.add_argument("--bits", default="8,4,2",
                    help="bit-widths to sweep (SPMD compile per "
                         "(bits, devices) point dominates on CPU — "
                         "narrow this for smokes)")
    args = ap.parse_args()
    main([int(v) for v in args.devices.split(",")], args.json, args.backend,
         tuple(int(v) for v in args.bits.split(",")))
