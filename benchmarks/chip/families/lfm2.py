"""LFM2-8B-A1B (``lfm2_moe``: gated short-conv layers beside GQA, MoE
feed-forwards) served by the program at W{w}A{a}: weights made on the
device from the seed, the program's `LMDecodeAdapter` under its
continuous-batching `Scheduler`, checked against `reference/lfm2.py`.

Weights follow `families/lm.py` (random codes on the symmetric W-bit
grid, per-channel scales that keep a projection's output at about the
scale of its input, norms near 1, the embedding's scale setting the
logits' spread) and add the leaves LFM2 has: the float32 router, whose
``router_gain`` sets the spread of its logits, a small selection-only
``expert_bias``, and the conv kernel (``conv_std``). ``write_gain``
scales, per stack (``conv_layers``, ``moe_ffn``, ...), the projections
that write to the residual stream. At equal scales the random conv
layers (a product of three projections) and the routing flips between
near-tied experts amplify bf16 rounding layer by layer until most served
tokens differ from the float32 reference's; writing those two kinds at
a fifth and a quarter of the others' scale keeps the served model close
to the reference (PERF.md, section 6).

The check is `families/lm.py`'s: the widest gap by which a served greedy
token's reference logit lies below the reference's best
(``max_logit_gap``) over a sample of finished requests, the reference
computed in blocks of `lm.REF_BATCH` sequences, and ``kv_bits_short``,
here over the K/V elements of the attention layers only, the only layers
that hold KV (the conv state adds to the bytes, never takes away).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from benchmarks.chip.families import lm
from benchmarks.chip.reference import lfm2 as reference

REF_BATCH = lm.REF_BATCH
# projections whose output is added to the residual stream
WRITES_RESIDUAL = ("out_proj", "wo")


def model_config(cfg: dict):
    """The program's ModelConfig for an ``lfm2_moe`` configuration file."""
    from repro.configs.base import ModelConfig, MoeSpec
    from repro.nn.layers import QuantConfig

    if "layer_types" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise ImportError("the program has no hybrid conv/attention LM "
                          "(ModelConfig.layer_types)")
    srv = cfg["serving"]
    qcfg = QuantConfig(mode="int", w_bits=srv["w_bits"],
                       a_bits=srv["a_bits"], a_absmax=srv["a_absmax"])
    return ModelConfig(
        name=cfg["name"], family="lm",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        act="swiglu", norm="rmsnorm", norm_eps=float(cfg["norm_eps"]),
        tie_embeddings=bool(cfg["tie_embeddings"]),
        rope_theta=float(cfg["rope_theta"]), qk_norm=True,
        layer_types=tuple(cfg["layer_types"]),
        n_dense_layers=cfg["num_dense_layers"], d_conv=cfg["conv_L_cache"],
        moe=MoeSpec(n_experts=cfg["num_experts"],
                    top_k=cfg["num_experts_per_tok"],
                    d_ff=cfg["moe_intermediate_size"], shared_expert=False,
                    router="sigmoid", expert_bias=bool(cfg["use_expert_bias"]),
                    norm_topk=bool(cfg["norm_topk_prob"]),
                    routed_scale=float(cfg["routed_scaling_factor"])),
        quant=qcfg,
        kv_quant_bits={"bfloat16": 16, "int8": 8}[srv["kv_dtype"]],
        param_dtype=srv["param_dtype"], compute_dtype=srv["compute_dtype"],
        remat=False)


def make_params(model, cfg: dict, seed: int):
    """The served parameter tree, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    w = cfg["weights"]
    w_bits = cfg["serving"]["w_bits"]
    pf = 8 // w_bits
    top = (1 << (w_bits - 1)) - 1
    code_std = math.sqrt(top * (top + 1) / 3.0)
    d = cfg["hidden_size"]
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(p.key) for p in path) for path, _ in flat]
    by_path = dict(zip(paths, (s for _, s in flat)))

    def normal(k, shape, std):
        return std * jax.random.normal(k, shape, jnp.float32)

    def leaf(path, sds, k):
        name = path.rsplit("/", 1)[-1]
        shape, dtype = sds.shape, sds.dtype
        if name == "w_packed":
            # a layer at a time: the codes' int32 temporaries of a whole
            # expert stack would not fit beside the served tree
            keys = jax.random.split(k, shape[0])
            return jax.lax.map(
                lambda kk: lm.random_packed(kk, shape[1:], w_bits), keys)
        if name == "w_scale":
            d_in = by_path[path[: -len("w_scale")] + "w_packed"].shape[-2] \
                * pf
            writes = path.split("/")[-2] in WRITES_RESIDUAL
            stack = path.split("/", 1)[0]
            gain = w["gain"] * (w.get("write_gain", {}).get(stack, 1.0)
                                if writes else 1.0)
            base = gain / (math.sqrt(d_in) * code_std)
            return (base * jax.random.uniform(k, shape, jnp.float32,
                                              0.8, 1.2)).astype(dtype)
        if name == "scale":
            return (1.0 + normal(k, shape, w["norm_jitter"])).astype(dtype)
        if name == "table":
            return normal(k, shape, w["embed_std"]).astype(dtype)
        if name == "router":
            return normal(k, shape, w["router_gain"] / math.sqrt(d)
                          ).astype(dtype)
        if name == "expert_bias":
            return normal(k, shape, w["expert_bias_std"]).astype(dtype)
        if name == "conv":
            return normal(k, shape, w["conv_std"]).astype(dtype)
        raise KeyError(f"no rule for parameter {path}")

    def gen(key):
        leaves = [leaf(p, s, jax.random.fold_in(key, lm._fnv(p)))
                  for p, s in zip(paths, by_path.values())]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(gen)(lm.seed_key(seed))


class Served(lm.Served):
    """The program's objects for one LFM2 cell."""

    def __init__(self, cfg: dict, traffic: dict, mesh=None):
        from repro.models.api import Model

        self.cfg = cfg
        self.traffic = traffic
        self.mesh = mesh
        self.model = Model(model_config(cfg))
        self.params = None

    def load(self, seed: int):
        import jax
        self.params = jax.block_until_ready(
            make_params(self.model, self.cfg, seed))

    def adapter(self):
        """The program's adapter, the cache donated to each step.
        Compiled for a v5e at the cell's 256 slots, the undonated step
        copies the KV stack (two 1.61 GB arrays) into fresh buffers every
        step, about 6.5 GB of HBM traffic (8 ms at 819 GB/s, a tenth of
        the step), and needs 12.9 GB against 10.3 GB donated."""
        from repro.serve.runtime import LMDecodeAdapter
        return LMDecodeAdapter(self.model, self.params,
                               max_len=self.traffic["max_len"], eos_id=-1,
                               mesh=self.mesh, donate_state=True)

    def _gap_stats(self, rows, masks, control=None) -> dict:
        """Over the sampled positions: the widest gap, the mean gap and
        the share of positions whose token is not the reference's best.
        The reference runs layer by layer, one block of sequences at a
        time."""
        import jax.numpy as jnp

        picked = []
        for i in range(0, len(rows), REF_BATCH):
            tok = jnp.asarray(np.stack(rows[i:i + REF_BATCH]))
            g = np.asarray(reference.gaps(self.params, tok, self.cfg,
                                          control=control))
            picked.append(g[np.stack(masks[i:i + REF_BATCH])])
        g = np.concatenate(picked)
        return {"max_logit_gap": float(g.max()),
                "mean_logit_gap": float(g.mean()),
                "miss_share": float(np.mean(g > 0))}

    def kv_bits_short(self, win) -> float:
        """Bits per K/V element of the attention layers that the state
        the window's steps carried falls short of the configuration's KV
        width (0 when it holds at least that many)."""
        if not win.state_bytes:
            return None
        c, t = self.cfg, self.traffic
        n_attn = sum(op == "full_attention" for op in c["layer_types"])
        elems = (2 * n_attn * t["slots"] * t["max_len"]
                 * c["num_key_value_heads"]
                 * (c["hidden_size"] // c["num_attention_heads"]))
        stated = {"bfloat16": 16, "int8": 8}[c["serving"]["kv_dtype"]]
        return max(0.0, stated - 8.0 * win.state_bytes / elems)


def build(cfg: dict, traffic: dict, mesh=None) -> Served:
    return Served(cfg, traffic, mesh)
