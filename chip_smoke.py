"""Bring-up check on a TPU: the packed sub-byte kernels at real widths,
ResNet-8 served at full width, and Qwen2.5-3B served at W4A8.

    python chip_smoke.py             # one chip: phases kernels, cnn, lm
    python chip_smoke.py --chips 4   # four chips: the cluster path only

Every phase runs in this one process (a chip belongs to one process) and
calls the functions the launch CLIs call. A phase that fails raises; the
script then exits non-zero and prints no result line. It also refuses to
run, without a result line, when JAX finds no TPU or when
``REPRO_QBACKEND`` names the interpreter or the numpy oracle. The last
line of a passing run is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Qwen2.5-3B dense widths (repro/configs/qwen2p5_3b.py)
D_MODEL, D_FF = 2048, 11008
GEMM_M = 256
# ResNet-8 conv geometries at 32x32 (repro/vision/configs/resnet8.py):
# name -> (h, cin, cout, f, stride, padding)
RESNET8_CONVS = {
    "stem": (32, 3, 16, 3, 1, 1),
    "s1/c": (32, 16, 16, 3, 1, 1),
    "s2/c1": (32, 16, 32, 3, 2, 1),
    "s2/c2": (16, 32, 32, 3, 1, 1),
    "s2/skip": (32, 16, 32, 1, 2, 0),
    "s3/c1": (16, 32, 64, 3, 2, 1),
    "s3/c2": (8, 64, 64, 3, 1, 1),
    "s3/skip": (16, 32, 64, 1, 2, 0),
}
CONV_BATCH = 8
SEED = 0            # random weights, activations and images
PIPELINES = ("off", "double_buffer")
# |logits(chip) - logits(host CPU)| bound for one Qwen decode step, as a
# share of max|logits(host)|: the integer GEMMs are exact on both, the
# bf16 float parts round differently, and bf16 logits near 200 have an
# ulp of 1.
LM_LOGIT_TOL = 0.05


class SmokeError(RuntimeError):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeError(msg)


def log(msg: str):
    print(msg, flush=True)


def on_pallas(fn, what: str, pipeline=None):
    """Run ``fn`` and require that every kernel dispatch it caused
    resolved to the ``pallas`` backend (and to ``pipeline``, if given)."""
    from repro import obs

    n0 = len(obs.dispatch_log())
    out = fn()
    events = obs.dispatch_log()[n0:]
    check(events, f"{what}: no kernel dispatch was recorded")
    for e in events:
        check(e["backend"] == "pallas",
              f"{what}: {e['op']} {e['shape']} resolved to "
              f"{e['backend']!r} ({e['backend_source']}), not 'pallas'")
        check(pipeline is None or e["pipeline"] == pipeline,
              f"{what}: {e['op']} ran pipeline {e['pipeline']!r}, "
              f"expected {pipeline!r}")
    return out


def bit_exact(got, want, what: str):
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape and got.dtype == want.dtype,
          f"{what}: {got.shape}/{got.dtype} vs {want.shape}/{want.dtype}")
    bad = int(np.sum(got != want))
    check(bad == 0, f"{what}: {bad} of {got.size} values differ")


# ------------------------------------------------------------ operands ---

def linear_params(rng, k: int, n: int, w_bits: int, a_bits: int):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import packing
    from repro.core.quantize import QuantizedLinearParams

    lo, hi = packing.int_range(w_bits, True)
    w = rng.integers(lo, hi + 1, size=(k, n), dtype=np.int8)
    return QuantizedLinearParams(
        w_packed=packing.pack(jnp.asarray(w), w_bits, axis=0),
        w_bits=w_bits, a_bits=a_bits, a_signed=False,
        kappa=jnp.asarray(rng.integers(-64, 64, n, dtype=np.int32)),
        lam=jnp.asarray(rng.integers(-2**16, 2**16, n, dtype=np.int32)),
        m=jnp.asarray(rng.integers(0, 2**15, n, dtype=np.int32)),
        d=18, out_bits=8, k_logical=k)


def segmented_params(rng, k: int, n: int, a_bits: int):
    """An 8|4|2 segment map over N (CHUNK-aligned thirds)."""
    import numpy as np

    from repro.core import packing
    from repro.core.quantize import quantize_linear_segmented

    third = n // 3 // packing.CHUNK * packing.CHUNK
    segmap = packing.SegmentMap(
        ((0, third, 8), (third, 2 * third, 4), (2 * third, n, 2)))
    w = np.concatenate([
        rng.integers(packing.int_range(b, True)[0],
                     packing.int_range(b, True)[1] + 1, size=(k, e - s),
                     dtype=np.int8) for s, e, b in segmap.runs], axis=1)
    return quantize_linear_segmented(
        w, segmap, rng.integers(-64, 64, n), rng.integers(-2**16, 2**16, n),
        rng.integers(0, 2**15, n), a_bits=a_bits, a_signed=False, d=18,
        out_bits=8)


def activations(rng, shape, a_bits: int):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import packing

    lo, hi = packing.int_range(a_bits, False)
    return jnp.asarray(rng.integers(lo, hi + 1, size=shape, dtype=np.int8))


def conv_params(rng, geo, w_bits: int, a_bits: int):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import calibrate_weight
    from repro.core.quantize import QuantSpec
    from repro.kernels.qconv import quantize_conv

    h, cin, cout, f, stride, pad = geo
    w = rng.normal(size=(f, f, cin, cout)).astype(np.float32) * 0.08
    sx = QuantSpec.activation(a_bits, 4.0)
    sy = QuantSpec.activation(a_bits, 8.0)
    return quantize_conv(
        jnp.asarray(w), calibrate_weight(jnp.asarray(w), w_bits),
        rng.normal(size=(cout,)).astype(np.float32) * .05 + .3,
        np.zeros((cout,), np.float32), sx, sy, stride, pad)


# -------------------------------------------------------------- phases ---

def phase_kernels(rng):
    """qdot / qdot_mixed / qconv through the registry at real widths, on
    the pallas backend in both pipeline modes, bit-exact vs xla."""
    from repro.kernels import api

    t0 = time.time()
    calls = 0
    for k, n in ((D_MODEL, D_FF), (D_FF, D_MODEL)):
        for wb, ab in ((8, 8), (4, 8), (2, 8), (4, 4), (2, 2)):
            p = linear_params(rng, k, n, wb, ab)
            x = activations(rng, (GEMM_M, k), ab)
            want = api.qdot(p, x, epilogue="raw", backend="xla")
            for pipe in PIPELINES:
                got = on_pallas(
                    lambda: api.qdot(p, x, epilogue="raw", pipeline=pipe),
                    f"qdot W{wb}A{ab} {k}->{n}", pipe)
                bit_exact(got, want, f"qdot W{wb}A{ab} {k}->{n} {pipe}")
                calls += 1
    for ab in (8, 4):
        p = segmented_params(rng, D_MODEL, D_FF, ab)
        x = activations(rng, (GEMM_M, D_MODEL), ab)
        want = api.qdot(p, x, epilogue="raw", backend="xla")
        for pipe in PIPELINES:
            got = on_pallas(
                lambda: api.qdot(p, x, epilogue="raw", pipeline=pipe),
                f"qdot_mixed 8|4|2 A{ab}", pipe)
            bit_exact(got, want, f"qdot_mixed 8|4|2 A{ab} {pipe}")
            calls += 1
    for name, geo in RESNET8_CONVS.items():
        h, cin = geo[0], geo[1]
        bits = [(8, 8), (4, 8), (2, 8)]
        if name in ("s2/c1", "s3/c2"):   # sub-byte activations too
            bits += [(4, 4), (2, 2)]
        for wb, ab in bits:
            p = conv_params(rng, geo, wb, ab)
            x = activations(rng, (CONV_BATCH, h, h, cin), ab)
            want = api.qconv(p, x, backend="xla")
            for pipe in PIPELINES:
                got = on_pallas(lambda: api.qconv(p, x, pipeline=pipe),
                                f"qconv {name} W{wb}A{ab}", pipe)
                bit_exact(got, want, f"qconv {name} W{wb}A{ab} {pipe}")
                calls += 1
    log(f"[kernels] {calls} pallas calls bit-exact vs xla: qdot "
        f"{D_MODEL}->{D_FF} and {D_FF}->{D_MODEL} at M={GEMM_M}, "
        f"qdot_mixed 8|4|2, qconv at {len(RESNET8_CONVS)} ResNet-8 "
        f"layers (batch {CONV_BATCH}), both pipeline modes "
        f"({time.time() - t0:.1f}s incl. compile)")


def deploy_resnet8():
    """The `repro.launch.vision` flow for resnet8 without --smoke:
    calibrate, plan over W8/4/2 at an auto budget, pack."""
    import numpy as np

    from repro.launch.vision import calibrate_and_plan
    from repro.vision.configs import get_vision_config
    from repro.vision.models import init_fp, quantize_net

    cfg = get_vision_config("resnet8", smoke=False, a_bits=8)
    rng = np.random.default_rng(SEED)
    fp_params = init_fp(cfg, seed=SEED)
    batches = [rng.uniform(0, 1, size=(4, *cfg.in_hw, cfg.in_ch)).astype(
        np.float32) for _ in range(2)]
    plan, absmax = calibrate_and_plan(cfg, fp_params, batches,
                                      candidates=(8, 4, 2), budget="auto")
    qnet = quantize_net(cfg, fp_params, absmax, plan=plan)
    images = rng.uniform(0, 1, size=(16, *cfg.in_hw, cfg.in_ch)).astype(
        np.float32)
    return qnet, images


def phase_cnn():
    """ResNet-8 at full width served through `VisionEngine` on the pallas
    backend in both pipeline modes, bit-exact vs the xla backend."""
    import numpy as np

    from repro.serve.engine import VisionEngine
    from repro.vision.models import forward_int, quantize_input

    t0 = time.time()
    qnet, images = deploy_resnet8()
    log(f"[cnn] resnet8 per-layer w_bits {qnet.layer_bits()}")
    want = np.asarray(forward_int(qnet, quantize_input(qnet, images),
                                  backend="xla"))
    for pipe in PIPELINES:
        os.environ["REPRO_QPIPELINE"] = pipe
        try:
            eng = VisionEngine(qnet, batch_size=8)
            got = on_pallas(lambda: eng.run(images),
                            f"resnet8 VisionEngine {pipe}", pipe)
        finally:
            del os.environ["REPRO_QPIPELINE"]
        bit_exact(got, want, f"resnet8 logits {pipe}")
        lat = eng.utilization_report()["latency_us"]
        log(f"[cnn] {pipe}: {len(images)} images in {lat['waves']} waves "
            f"of 8, logits bit-exact vs xla, preds "
            f"{got.argmax(-1).tolist()}")
    log(f"[cnn] done ({time.time() - t0:.1f}s incl. calibration and "
        "compile)")


def phase_lm():
    """Qwen2.5-3B at full width and depth, W4A8, through the
    `repro.launch.serve` flow; one decode step compared with the host
    CPU run of the same packed params."""
    import jax
    import numpy as np

    from repro.launch.serve import build_serving, make_requests
    from repro.models.api import get_config
    from repro.nn.module import param_bytes
    from repro.serve.engine import Engine

    t0 = time.time()
    cfg = get_config("qwen2.5-3b")
    model, params, _, mode = build_serving(cfg, quant="w4a8", seed=SEED)
    log(f"[lm] {cfg.name} [{mode}] {cfg.n_layers} layers d={cfg.d_model} "
        f"ff={cfg.d_ff} vocab={cfg.vocab}: packed params "
        f"{param_bytes(params):,} bytes, built on the host in "
        f"{time.time() - t0:.1f}s")

    reqs = make_requests(cfg, 4, 16, SEED)
    eng = Engine(model, params, batch_size=4, max_len=64)
    t1 = time.time()
    out = eng.generate(reqs)
    dt = time.time() - t1
    toks = [len(r.out) for r in out]
    check(all(1 <= t <= 16 for t in toks), f"[lm] tokens out {toks}")
    log(f"[lm] served {len(out)} requests at batch 4: tokens out {toks} "
        f"in {dt:.1f}s incl. compile")

    dev = jax.devices()[0]
    cpu = jax.local_devices(backend="cpu")[0]
    token = np.asarray([[int(r.prompt[0])] for r in reqs], np.int32)
    pos = np.zeros((4,), np.int32)
    step = jax.jit(model.decode)
    # the padded vocab tail is masked to -1e9 on both; compare the rest
    chip = np.asarray(step(params, model.init_cache(4, 64), token, pos)[0],
                      np.float32)[..., :cfg.vocab]
    with jax.default_device(cpu):
        host = np.asarray(step(jax.device_put(params, cpu),
                               model.init_cache(4, 64), token, pos)[0],
                          np.float32)[..., :cfg.vocab]
    check(np.isfinite(chip).all(), "[lm] non-finite logits on the chip")
    diff = float(np.max(np.abs(chip - host)))
    scale = float(np.max(np.abs(host)))
    log(f"[lm] one decode step, logits {chip.shape}: max|chip-host| "
        f"{diff:.4f}, max|host| {scale:.4f}, bound "
        f"{LM_LOGIT_TOL} * max|host| = {LM_LOGIT_TOL * scale:.4f}")
    check(diff <= LM_LOGIT_TOL * scale, "[lm] chip logits off the host run")
    stats = dev.memory_stats() or {}
    log(f"[lm] peak_bytes_in_use {stats.get('peak_bytes_in_use')} on "
        f"{dev.device_kind} ({time.time() - t0:.1f}s for the phase)")


def phase_cluster(devs):
    """The cluster path on four chips: sharded qdot/qconv on (2,2) and
    (4,1) meshes bit-exact vs one chip, and the ResNet-8 VisionEngine at
    dp=4 bit-exact vs meshless."""
    import numpy as np

    from repro.kernels import api
    from repro.parallel.ctx import make_mesh
    from repro.serve.engine import VisionEngine

    rng = np.random.default_rng(SEED)
    meshes = {(dp, tp): make_mesh((dp, tp), ("data", "model"), devices=devs)
              for dp, tp in ((2, 2), (4, 1))}
    t0 = time.time()

    def on_all_devices(arr, what):
        used = {s.device for s in arr.addressable_shards if s.data.size}
        check(used == set(devs),
              f"{what}: result shards on {len(used)} of {len(devs)} chips")

    for wb, ab in ((8, 8), (4, 8), (2, 8)):
        p = linear_params(rng, D_MODEL, D_FF, wb, ab)
        x = activations(rng, (GEMM_M, D_MODEL), ab)
        want = on_pallas(lambda: api.qdot(p, x), f"qdot W{wb}A{ab} 1 chip")
        for shape, mesh in meshes.items():
            got = on_pallas(lambda: api.qdot(p, x, mesh=mesh),
                            f"qdot W{wb}A{ab} mesh {shape}")
            on_all_devices(got, f"qdot W{wb}A{ab} mesh {shape}")
            bit_exact(got, want, f"qdot W{wb}A{ab} mesh {shape}")
    for name, geo in RESNET8_CONVS.items():
        h, cin = geo[0], geo[1]
        p = conv_params(rng, geo, 4, 8)
        x = activations(rng, (CONV_BATCH, h, h, cin), 8)
        want = on_pallas(lambda: api.qconv(p, x), f"qconv {name} 1 chip")
        for shape, mesh in meshes.items():
            got = on_pallas(lambda: api.qconv(p, x, mesh=mesh),
                            f"qconv {name} mesh {shape}")
            on_all_devices(got, f"qconv {name} mesh {shape}")
            bit_exact(got, want, f"qconv {name} mesh {shape}")
    log(f"[cluster] sharded qdot (W8/W4/W2, {D_MODEL}->{D_FF}) and qconv "
        f"({len(RESNET8_CONVS)} ResNet-8 layers) on meshes "
        f"{sorted(meshes)} bit-exact vs one chip, shards on all "
        f"{len(devs)} chips ({time.time() - t0:.1f}s incl. compile)")

    qnet, images = deploy_resnet8()
    want = on_pallas(lambda: VisionEngine(qnet, batch_size=8).run(images),
                     "resnet8 meshless")
    eng = VisionEngine(qnet, batch_size=8, mesh=meshes[(4, 1)])
    got = on_pallas(lambda: eng.run(images), "resnet8 dp=4")
    bit_exact(got, want, "resnet8 dp=4 logits")
    per = eng.utilization_report()["per_device"]
    check(len(per) == len(devs) and min(per) > 0,
          f"[cluster] dp=4 per-device utilization {per}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    check(all(peaks), f"[cluster] a chip allocated nothing: {peaks}")
    log(f"[cluster] resnet8 VisionEngine dp=4: logits bit-exact vs "
        f"meshless, per-device utilization {per}, peak_bytes_in_use "
        f"per chip {peaks}")


# ---------------------------------------------------------------- main ---

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the cluster path on four chips")
    args = ap.parse_args()

    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    # phase lm compares with the host CPU backend, so keep it available
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    cache_dir = enable_compile_cache()

    import jax
    import numpy as np

    from repro import obs
    from repro.kernels.api import ENV_VAR

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} devices", file=sys.stderr)
        return 1
    if os.environ.get(ENV_VAR) in ("pallas_interpret", "eager_ref"):
        print(f"chip_smoke: {ENV_VAR}={os.environ[ENV_VAR]} would keep "
              "the kernels off the chip", file=sys.stderr)
        return 1
    obs.enable()
    cache_events = {"hits": 0, "misses": 0}

    def count_cache_event(event, **_):
        for k in cache_events:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache_events[k] += 1

    jax.monitoring.register_event_listener(count_cache_event)
    log(f"device {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"compile cache {cache_dir}")

    t0 = time.time()
    if args.chips == 4:
        phase_cluster(devs[:4])
    else:
        phase_kernels(np.random.default_rng(SEED))
        phase_cnn()
        phase_lm()
    log(f"all phases passed in {time.time() - t0:.1f}s; persistent "
        f"compile cache: {cache_events['hits']} hits, "
        f"{cache_events['misses']} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
