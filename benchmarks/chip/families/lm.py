"""A dense GQA decoder LM served by the program at W{w}A{a}: weights made
on the device from the seed, the program's `LMDecodeAdapter` under its
continuous-batching `Scheduler`, checked against `reference/lm.py`.

Weights: one jitted call builds the whole served tree in its served
types. Every ``w_packed`` container holds random codes on the symmetric
W-bit grid (`random_packed`), each ``w_scale`` keeps a projection's
output at about the scale of its input, norms sit near 1 and biases near
0, and the embedding's scale sets the logits' spread. No float32 weight
tree is built and nothing is packed on the host.

The check: once the window has closed, a sample of finished requests
drawn from the seed, the one with the most served tokens among them, is
run through the reference over prompt plus served tokens. The numbers
compared are the widest gap by which a served (greedy) token's reference
logit lies below the reference's best logit at that position
(``max_logit_gap``), and how many bits short of the configuration's KV
width the cache that the window's steps carried was (``kv_bits_short``:
the state's bytes over the K/V elements a cache of the cell's slots and
``max_len`` holds, so a cache stored narrower than stated reads above 0).
"""
from __future__ import annotations

import math

import numpy as np

from benchmarks.chip import loadgen
from benchmarks.chip.reference import lm as reference

REF_BATCH = 4        # sequences per reference call


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    from repro.nn.layers import QuantConfig

    srv = cfg["serving"]
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"unsupported hidden_act {cfg['hidden_act']!r}")
    qcfg = QuantConfig(mode="int", w_bits=srv["w_bits"],
                       a_bits=srv["a_bits"], a_absmax=srv["a_absmax"])
    return ModelConfig(
        name=cfg["name"], family="lm",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        act="swiglu", norm="rmsnorm", qkv_bias=bool(cfg["qkv_bias"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]), quant=qcfg,
        kv_quant_bits={"bfloat16": 16, "int8": 8}[srv["kv_dtype"]],
        param_dtype=srv["param_dtype"], compute_dtype=srv["compute_dtype"],
        remat=False)


def _fnv(s: str) -> int:
    h = 2166136261
    for ch in s:
        h = (h ^ ord(ch)) * 16777619 & 0xFFFFFFFF
    return h


def seed_key(seed: int):
    import jax
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def random_packed(key, shape, bits: int):
    """int8 containers of ``shape`` whose ``bits``-wide fields hold codes
    uniform on the symmetric grid [-(2^(b-1) - 1), 2^(b-1) - 1], the grid
    the program's quantizer produces. (Uniform random bytes would hold
    codes of mean -1/2: a rank-one term in every projection that, over 36
    layers, drives every position to the same greedy token.) The fields
    are independent, so the packing layout does not matter."""
    import jax
    import jax.numpy as jnp

    pf = 8 // bits
    top = (1 << (bits - 1)) - 1
    codes = jax.random.randint(key, (pf,) + tuple(shape), -top, top + 1,
                               jnp.int8).astype(jnp.int32)
    mask = (1 << bits) - 1
    word = codes[0] & mask
    for p in range(1, pf):
        word = word | ((codes[p] & mask) << (bits * p))
    return jnp.where(word > 127, word - 256, word).astype(jnp.int8)


def make_params(model, cfg: dict, seed: int):
    """The served parameter tree, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    w = cfg["weights"]
    w_bits = cfg["serving"]["w_bits"]
    pf = 8 // w_bits
    top = (1 << (w_bits - 1)) - 1
    code_std = math.sqrt(top * (top + 1) / 3.0)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(p.key) for p in path) for path, _ in flat]
    by_path = dict(zip(paths, (s for _, s in flat)))

    def leaf(path, sds, k):
        name = path.rsplit("/", 1)[-1]
        shape, dtype = sds.shape, sds.dtype
        if name == "w_packed":
            return random_packed(k, shape, w_bits)
        if name == "w_scale":
            d_in = by_path[path[: -len("w_scale")] + "w_packed"].shape[-2] \
                * pf
            base = w["gain"] / (math.sqrt(d_in) * code_std)
            return (base * jax.random.uniform(k, shape, jnp.float32,
                                              0.8, 1.2)).astype(dtype)
        if name == "scale":
            return (1.0 + w["norm_jitter"] * jax.random.normal(
                k, shape, jnp.float32)).astype(dtype)
        if name == "b":
            return (w["bias_std"] * jax.random.normal(
                k, shape, jnp.float32)).astype(dtype)
        if name == "table":
            return (w["embed_std"] * jax.random.normal(
                k, shape, jnp.float32)).astype(dtype)
        raise KeyError(f"no rule for parameter {path}")

    def gen(key):
        leaves = [leaf(p, s, jax.random.fold_in(key, _fnv(p)))
                  for p, s in zip(paths, by_path.values())]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(gen)(seed_key(seed))


class Served:
    """The program's objects for one LM cell."""

    def __init__(self, cfg: dict, traffic: dict, mesh=None):
        from repro.models.api import Model

        self.cfg = cfg
        self.traffic = traffic
        self.mesh = mesh
        self.model = Model(model_config(cfg))
        self.params = None
        self._gaps = {}

    # ---- set-up ----
    def load(self, seed: int):
        import jax
        self.params = jax.block_until_ready(
            make_params(self.model, self.cfg, seed))

    def adapter(self):
        from repro.serve.runtime import LMDecodeAdapter
        # no EOS: with random weights it would end requests at random,
        # and the mix fixes every request's output length
        return LMDecodeAdapter(self.model, self.params,
                               max_len=self.traffic["max_len"], eos_id=-1,
                               mesh=self.mesh)

    def requests(self, seconds: float, seed: int):
        """(offsets, payloads) of a run."""
        from repro.serve.runtime import Request
        offsets, prompts, outs = loadgen.lm_requests(
            self.traffic, self.cfg["vocab_size"], seconds, seed)
        return offsets, [Request(prompt=p, max_new_tokens=o)
                         for p, o in zip(prompts, outs)]

    def warm_payloads(self):
        """Two tiny requests: one step compiles the cell's one shape."""
        from repro.serve.runtime import Request
        return [Request(prompt=np.array([1, 2], np.int32), max_new_tokens=2)
                for _ in range(2)]

    # ---- the check ----
    def _sample(self, win, seed: int):
        """Rows (prompt + served tokens, padded to max_len) and masks of
        the positions whose logits chose a served token, for the sampled
        finished requests; None when nothing finished."""
        done = [r for r in win.finished if len(win.finished[r].out)]
        if not done:
            return None
        longest = max(done, key=lambda r: len(win.finished[r].out))
        pick = loadgen.sample(done, self.traffic["check_sample"], [longest],
                              seed)
        t = self.traffic["max_len"]
        rows, masks = [], []
        for r in pick:
            req = win.finished[r]
            p, n = len(req.prompt), len(req.out)
            row = np.zeros(t, np.int32)
            row[:p] = req.prompt
            row[p:p + n] = req.out
            mask = np.zeros(t - 1, bool)
            mask[p - 1: p - 1 + n] = True      # logits that chose out[k]
            rows.append(row)
            masks.append(mask)
        while len(rows) % REF_BATCH:
            rows.append(np.zeros(t, np.int32))
            masks.append(np.zeros(t - 1, bool))
        self.last_check = {"requests": len(pick),
                           "tokens": int(sum(m.sum() for m in masks)),
                           "longest": len(win.finished[longest].out)}
        return rows, masks

    def _gap_stats(self, rows, masks, control=None) -> dict:
        """Over the sampled positions: the widest gap, the mean gap and
        the share of positions whose token is not the reference's best."""
        import jax
        import jax.numpy as jnp

        key = None if control is None else tuple(sorted(control.items()))
        if key not in self._gaps:
            cfg = self.cfg
            self._gaps[key] = jax.jit(
                lambda p, tok: reference.gaps(p, tok, cfg, control=control))
        picked = []
        for i in range(0, len(rows), REF_BATCH):
            tok = jnp.asarray(np.stack(rows[i:i + REF_BATCH]))
            g = np.asarray(self._gaps[key](self.params, tok))
            picked.append(g[np.stack(masks[i:i + REF_BATCH])])
        g = np.concatenate(picked)
        return {"max_logit_gap": float(g.max()),
                "mean_logit_gap": float(g.mean()),
                "miss_share": float(np.mean(g > 0))}

    def kv_bits_short(self, win) -> float:
        """Bits per K/V element that the state the window's steps carried
        falls short of the configuration's KV width (0 when it holds at
        least that many)."""
        if not win.state_bytes:
            return None
        c, t = self.cfg, self.traffic
        elems = (2 * c["num_hidden_layers"] * t["slots"] * t["max_len"]
                 * c["num_key_value_heads"]
                 * (c["hidden_size"] // c["num_attention_heads"]))
        stated = {"bfloat16": 16, "int8": 8}[c["serving"]["kv_dtype"]]
        return max(0.0, stated - 8.0 * win.state_bytes / elems)

    def check(self, win, seed: int) -> dict:
        """{"max_logit_gap": ..., "kv_bits_short": ...}, each (value,
        limit); the sample's mean gap and miss share go to
        ``last_check``."""
        lim = self.cfg["check"]
        out = {"max_logit_gap": (None, lim["max_logit_gap"]),
               "kv_bits_short": (self.kv_bits_short(win),
                                 lim["kv_bits_short"])}
        sample = self._sample(win, seed)
        if sample is not None:
            stats = self._gap_stats(*sample)
            self.last_check.update(stats)
            out["max_logit_gap"] = (stats["max_logit_gap"],
                                    lim["max_logit_gap"])
        return out

    def control(self, win, seed: int, variant: dict) -> dict:
        """The gap statistics of the tokens that the reference computed
        at ``variant``'s lower precision puts first, on the same sample."""
        rows, masks = self._sample(win, seed)
        return self._gap_stats(rows, masks, control=variant)

    def rebind(self, adapter):
        """Point an adapter built by `adapter()` at the current weights."""
        adapter.params = self.params
        return adapter


def build(cfg: dict, traffic: dict, mesh=None) -> Served:
    return Served(cfg, traffic, mesh)
