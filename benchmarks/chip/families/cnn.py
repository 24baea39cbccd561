"""An integer CNN (PULP-NN layer set) served by the program's
`VisionAdapter` under its continuous-batching `Scheduler`, checked
against `reference/cnn.py`.

The network is drawn at the integer level, from the seed, in one jitted
call: each conv's weight codes uniform on its plan's symmetric W-bit
grid, its integer batch-norm multipliers ``kappa`` uniform in
[kappa_min, 127] and offsets ``lam`` normal. The requantization
constants (m, d) of every conv, add and pool are fixed by the
configuration's ``requant`` recipe and do not depend on the seed: each
conv's output spread is set from the expected spread of its integer
accumulator. The codes are packed into the program's containers by the
program's own packer (part of what the check covers); the reference
reads the unpacked codes. Set-up runs no calibration.

The check compares the raw integer logits of a sample of the answers
served in the window, drawn from the seed, with the reference's:
``mismatched_answers`` counts the sampled answers that differ at all.
"""
from __future__ import annotations

import math

import numpy as np

from benchmarks.chip import costs, loadgen
from benchmarks.chip.reference import cnn as reference

_A_MAX = {8: 127, 4: 15, 2: 3}
M_BITS, D_MAX = 15, 31


def _w_max(bits: int) -> int:
    return (1 << (bits - 1)) - 1        # symmetric grid: 7 at W4, 1 at W2


def pick_md(ratio: float, d_min: int) -> tuple:
    """(m, d) with m = round(ratio * 2^d) < 2^15 and d as large as fits."""
    d = min(D_MAX, int(math.floor(math.log2((1 << M_BITS) - 1)
                                  - math.log2(ratio))))
    if d < d_min:
        raise ValueError(f"requant ratio {ratio} needs d={d} < {d_min}")
    return int(round(ratio * (1 << d))), d


def requant_constants(cfg: dict, a_bits: int) -> dict:
    """Per layer path, the seed-independent integers of the recipe."""
    rq = cfg["requant"]
    scale = _A_MAX[a_bits] / _A_MAX[cfg["a_bits"]]
    kappa_mean = (rq["kappa_min"] + 127) / 2.0
    out = {}
    for t in costs.cnn_layer_shapes(cfg):
        L = t["layer"]
        kind = L["kind"]
        if kind == "conv":
            bits = cfg["plan"][L["path"]]
            wm = _w_max(bits)
            w_rms = math.sqrt(wm * (wm + 1) / 3.0)
            x_rms = (rq["input_rms"] if L["path"] == cfg["layers"][0]["path"]
                     else rq["act_rms"])
            acc_std = math.sqrt(L["k"] * L["k"] * t["in"][2]) * x_rms * w_rms
            m, d = pick_md(rq["target_std"] * scale / (acc_std * kappa_mean),
                           16)
            out[L["path"]] = {"m": m, "d": d, "acc_std": acc_std,
                              "kappa_mean": kappa_mean}
        elif kind == "add":
            m, d = pick_md(rq["add_ratio"], 0)
            out[L["path"]] = {"m1": m, "m2": m, "d": d}
        elif kind == "avgpool_global":
            h, w, _ = t["in"]
            m, d = pick_md(rq["pool_gain"] / (h * w), 16)
            out[L["path"]] = {"m": m, "d": d}
    return out


def make_codes(cfg: dict, seed: int):
    """Per conv / linear layer: codes, kappa, lam and both packed weight
    layouts, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    from repro.core import packing

    from benchmarks.chip.families.lm import _fnv, seed_key

    rq = cfg["requant"]
    consts = requant_constants(cfg, cfg["a_bits"])
    layers = [t for t in costs.cnn_layer_shapes(cfg)
              if t["layer"]["kind"] in ("conv", "linear")]

    def gen(key):
        out = {}
        for t in layers:
            L = t["layer"]
            path = L["path"]
            bits = cfg["plan"][path]
            wm = _w_max(bits)
            k = jax.random.fold_in(key, _fnv(path))
            kw, kk, kl = jax.random.split(k, 3)
            cin, cout = t["in"][2], L["cout"]
            taps = L["k"] * L["k"] if L["kind"] == "conv" else 1
            codes = jax.random.randint(kw, (taps * cin, cout), -wm, wm + 1,
                                       jnp.int32).astype(jnp.int8)
            w_packed = packing.pack(packing.pad_to_chunk(codes, axis=0),
                                    bits, axis=0)
            entry = {"codes": codes, "w_packed": w_packed}
            if L["kind"] == "conv":
                cin_pad = packing.padded_size(cin)
                tap = jnp.pad(codes.reshape(taps, cin, cout),
                              ((0, 0), (0, cin_pad - cin), (0, 0)))
                entry["w_packed_fused"] = packing.pack(
                    tap.reshape(taps * cin_pad, cout), bits, axis=0)
                c = consts[path]
                entry["kappa"] = jax.random.randint(
                    kk, (cout,), rq["kappa_min"], 128, jnp.int32)
                entry["lam"] = jnp.round(
                    jax.random.normal(kl, (cout,)) * rq["lam_frac"]
                    * c["acc_std"] * c["kappa_mean"]).astype(jnp.int32)
            out[path] = entry
        return out

    return jax.jit(gen)(seed_key(seed)), consts


def to_program(cfg: dict, codes: dict, consts: dict):
    """The program's QuantizedVisionNet over the drawn integers."""
    import jax.numpy as jnp

    from repro.core import packing
    from repro.core.quantize import QuantizedLinearParams, QuantSpec
    from repro.kernels.qconv.ops import QuantizedConvParams
    from repro.vision.configs import get_vision_config
    from repro.vision.layers import QAvgPool2D, QConv2D, QLinear, \
        QResidualAdd
    from repro.vision.models import QuantizedVisionNet, trace_shapes

    a = cfg["a_bits"]
    vcfg = get_vision_config(cfg["program_config"], a_bits=a)
    mine = costs.cnn_layer_shapes(cfg)
    theirs = trace_shapes(vcfg)
    if [(t["layer"]["path"], tuple(t["out"])) for t in mine] != \
            [(t["layer"].path, tuple(t["out"])) for t in theirs]:
        raise ValueError("configuration layers differ from the program's "
                         f"{cfg['program_config']!r} graph")
    qlayers = []
    for t, L in zip(mine, vcfg.layers):
        j = t["layer"]
        c = codes.get(j["path"])
        k = consts.get(j["path"])
        if j["kind"] == "conv":
            cin = t["in"][2]
            gemm = QuantizedLinearParams(
                w_packed=c["w_packed"], w_bits=cfg["plan"][j["path"]],
                a_bits=a, a_signed=False, kappa=c["kappa"], lam=c["lam"],
                m=jnp.full((j["cout"],), k["m"], jnp.int32), d=k["d"],
                out_bits=a, k_logical=j["k"] * j["k"] * cin)
            q = QConv2D(conv=QuantizedConvParams(
                gemm=gemm, fh=j["k"], fw=j["k"], stride=j["stride"],
                padding=j["pad"], cin=cin, cout=j["cout"],
                w_packed_fused=c["w_packed_fused"],
                cin_pad=packing.padded_size(cin)))
        elif j["kind"] == "add":
            q = QResidualAdd(m1=k["m1"], m2=k["m2"], d=k["d"], out_bits=a)
        elif j["kind"] == "avgpool_global":
            q = QAvgPool2D(window=0, stride=1, m=k["m"], d=k["d"],
                           out_bits=a)
        else:
            n = j["cout"]
            q = QLinear(gemm=QuantizedLinearParams(
                w_packed=c["w_packed"], w_bits=cfg["plan"][j["path"]],
                a_bits=a, a_signed=False, kappa=jnp.ones((n,), jnp.int32),
                lam=jnp.zeros((n,), jnp.int32), m=jnp.ones((n,), jnp.int32),
                d=16, out_bits=8, k_logical=t["in"][2]), epilogue="raw")
        qlayers.append((L, q))
    spec = QuantSpec.activation(a, input_eps(cfg, a) * _A_MAX[a])
    return QuantizedVisionNet(cfg=vcfg, qlayers=tuple(qlayers),
                              input_spec=spec, eps_logits=1.0)


def input_eps(cfg: dict, a_bits: int) -> float:
    """The input grid: 1/128 at 8 bits, a power of two at every width so
    that quantizing a grid pixel is exact on any device."""
    return 2.0 ** -round(math.log2(_A_MAX[a_bits] + 1))


def reference_net(cfg: dict, codes: dict, consts: dict) -> dict:
    """The reference's integers (host NumPy) from the drawn codes."""
    net = {}
    for t in costs.cnn_layer_shapes(cfg):
        j = t["layer"]
        path = j["path"]
        if j["kind"] == "conv":
            c = codes[path]
            w = np.asarray(c["codes"], np.int64).reshape(
                j["k"], j["k"], t["in"][2], j["cout"])
            net[path] = {"w": w, "kappa": np.asarray(c["kappa"]),
                         "lam": np.asarray(c["lam"]),
                         "m": consts[path]["m"], "d": consts[path]["d"]}
        elif j["kind"] == "linear":
            net[path] = {"w": np.asarray(codes[path]["codes"], np.int64)}
        else:
            net[path] = dict(consts[path])
    return net


class Served:
    """The program's objects for one CNN cell."""

    def __init__(self, cfg: dict, traffic: dict, mesh=None):
        self.cfg = cfg
        self.traffic = traffic
        self.mesh = mesh
        self.qnet = None
        self.codes = None
        self.consts = None
        self.images = None

    def load(self, seed: int):
        import jax
        codes, consts = make_codes(self.cfg, seed)
        self.codes = jax.block_until_ready(codes)
        self.consts = consts
        self.qnet = to_program(self.cfg, self.codes, consts)

    def adapter(self):
        from repro.serve.runtime import VisionAdapter
        return VisionAdapter(self.qnet, mesh=self.mesh)

    def requests(self, seconds: float, seed: int):
        shape = (*self.cfg["in_hw"], self.cfg["in_ch"])
        offsets, imgs = loadgen.images(self.traffic, shape, seconds, seed)
        self.images = imgs
        return offsets, list(imgs)

    def warm_payloads(self):
        shape = (*self.cfg["in_hw"], self.cfg["in_ch"])
        return [np.zeros(shape, np.float32) for _ in range(2)]

    def reference_logits(self, idx: list, a_bits: int) -> np.ndarray:
        consts = (self.consts if a_bits == self.cfg["a_bits"]
                  else requant_constants(self.cfg, a_bits))
        net = reference_net(self.cfg, self.codes, consts)
        x = reference.quantize_input(self.images[idx],
                                     input_eps(self.cfg, a_bits), a_bits)
        return reference.forward(net, self.cfg, x, a_bits)

    def _sample(self, win, seed: int):
        rids = sorted(r for r in win.finished if win.in_window(r))
        if not rids:
            return None
        pick = loadgen.sample(rids, self.traffic["check_sample"], [], seed)
        self.last_check = {"answers": len(pick)}
        return pick, [win.index[r] for r in pick]

    def check(self, win, seed: int) -> dict:
        """{"mismatched_answers": (count, limit)} over the sample."""
        limit = self.cfg["check"]["mismatched_answers"]
        sample = self._sample(win, seed)
        if sample is None:
            return {"mismatched_answers": (None, limit)}
        pick, idx = sample
        want = self.reference_logits(idx, self.cfg["a_bits"])
        got = np.stack([np.asarray(win.finished[r], np.int64) for r in pick])
        return {"mismatched_answers":
                (int(np.sum(np.any(got != want, axis=-1))), limit)}

    def control(self, win, seed: int, variant: dict) -> float:
        """Sampled answers on which the reference at ``variant``'s lower
        activation width differs from the reference as configured."""
        _, idx = self._sample(win, seed)
        want = self.reference_logits(idx, self.cfg["a_bits"])
        low = self.reference_logits(idx, variant["a_bits"])
        return int(np.sum(np.any(low != want, axis=-1)))

    def rebind(self, adapter):
        """A new adapter over the current weights (the net is baked into
        the adapter's jitted forward)."""
        return self.adapter()


def build(cfg: dict, traffic: dict, mesh=None) -> Served:
    return Served(cfg, traffic, mesh)
