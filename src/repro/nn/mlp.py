"""FFN (SwiGLU/GeGLU/GELU) and Mixture-of-Experts with expert parallelism.

Training MoE uses group-limited one-hot einsum dispatch (GShard-style
with capacity factor), sized so the dispatch tensors stay modest; experts
are sharded over the `model` mesh axis (EP). Sub-byte expert weights are the
single biggest win of the paper's technique at LM scale: expert streaming is
memory-bound, so packed int4/int2 experts cut the dominant roofline term by
2-4x (see EXPERIMENTS.md).

Serving MoE (`moe_dropless`, and every ``QuantConfig(mode="int")`` layer)
drops nothing: every held expert runs on every token through one grouped
int GEMM (`repro.kernels.api.xla_grouped_int_gemm`), so each expert's
packed weights stream once a step, and the routing weights, zero off the
top k, combine the outputs. Compute is ``n_experts / top_k`` times the
routed work; bytes are what a grouped kernel would stream once every
expert is hit, as every one is in a batch of a few hundred tokens.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.deploy.policy import PrecisionPlan, resolve_qcfg
from repro.core import packing
from repro.nn.layers import (QOFF, QuantConfig, dense_apply, dense_def,
                             quantize_activations)
from repro.nn.module import ParamDef
from repro.parallel.ctx import constrain


@dataclasses.dataclass(frozen=True)
class MlpConfig:
    d_model: int
    d_ff: int
    act: str = "swiglu"          # swiglu | geglu | gelu
    qcfg: QuantConfig = QOFF
    # mixed-precision deployment: per-dense override of qcfg, resolved by
    # this block's param path (e.g. "layers/mlp") + the dense name
    plan: Optional[PrecisionPlan] = None
    path: str = "layers/mlp"

    def q(self, name: str) -> QuantConfig:
        return resolve_qcfg(self.plan, f"{self.path}/{name}", self.qcfg)


def mlp_def(cfg: MlpConfig, dtype=jnp.float32):
    gated = cfg.act in ("swiglu", "geglu")
    p = {"wi": dense_def(cfg.d_model, cfg.d_ff, ("embed", "mlp"),
                         qcfg=cfg.q("wi"), dtype=dtype),
         "wo": dense_def(cfg.d_ff, cfg.d_model, ("mlp", "embed"),
                         qcfg=cfg.q("wo"), dtype=dtype)}
    if gated:
        p["wg"] = dense_def(cfg.d_model, cfg.d_ff, ("embed", "mlp"),
                            qcfg=cfg.q("wg"), dtype=dtype)
    return p


def _act(h, g, kind):
    if kind == "swiglu":
        return jax.nn.silu(g) * h
    if kind == "geglu":
        return jax.nn.gelu(g) * h
    return jax.nn.gelu(h)


def mlp_apply(p, x, cfg: MlpConfig):
    h = constrain(dense_apply(p["wi"], x, qcfg=cfg.q("wi")),
                  ("batch", None, "mlp"))
    g = dense_apply(p["wg"], x, qcfg=cfg.q("wg")) if "wg" in p else None
    if g is not None:
        g = constrain(g, ("batch", None, "mlp"))
    y = dense_apply(p["wo"], _act(h, g, cfg.act), qcfg=cfg.q("wo"))
    return constrain(y, ("batch", None, None))


# ------------------------------------------------------------------ MoE ---

@dataclasses.dataclass(frozen=True)
class MoeConfig:
    d_model: int
    d_ff: int                 # per-expert hidden
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    group_size: int = 1024    # tokens per dispatch group
    shared_expert: bool = True
    act: str = "swiglu"
    qcfg: QuantConfig = QOFF
    plan: Optional[PrecisionPlan] = None
    path: str = "layers/moe"
    router: str = "softmax"       # softmax | sigmoid (see MoeSpec)
    expert_bias: bool = False
    norm_topk: bool = False
    routed_scale: float = 1.0

    def capacity(self, tokens_per_group: int) -> int:
        c = int(tokens_per_group * self.top_k * self.capacity_factor
                / self.n_experts) + 1
        return max(c, 4)


def _expert_def(e, d_in, d_out, axes, qcfg: QuantConfig, dtype):
    """One projection of every expert: float (E, d_in, d_out), or under
    int mode chunk-planar W-bit containers packed along d_in with float32
    per-channel scales, a dense layer's layout per expert."""
    if qcfg.mode != "int":
        return ParamDef((e, d_in, d_out), ("experts",) + axes, "normal",
                        dtype)
    kp = packing.padded_size(d_in) // packing.pack_factor(qcfg.w_bits)
    return {"w_packed": ParamDef((e, kp, d_out), ("experts",) + axes,
                                 "zeros", jnp.int8),
            "w_scale": ParamDef((e, d_out), ("experts", axes[1]), "ones",
                                jnp.float32)}


def moe_def(cfg: MoeConfig, dtype=jnp.float32):
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    q = cfg.qcfg
    p = {
        "router": ParamDef((d, e), ("embed", "experts"), "normal", dtype,
                           scale=0.02),
        "wi": _expert_def(e, d, f, ("embed", "expert_mlp"), q, dtype),
        "wg": _expert_def(e, d, f, ("embed", "expert_mlp"), q, dtype),
        "wo": _expert_def(e, f, d, ("expert_mlp", "embed"), q, dtype),
    }
    if cfg.expert_bias:
        p["expert_bias"] = ParamDef((e,), ("experts",), "zeros", dtype)
    if cfg.shared_expert:
        p["shared"] = mlp_def(
            MlpConfig(d, f, cfg.act, cfg.qcfg, cfg.plan,
                      f"{cfg.path}/shared"), dtype)
    return p


def moe_apply(p, x, cfg: MoeConfig):
    """x: (B, S, d). Group-limited scatter/gather dispatch with capacity
    dropping.

    The classic GShard one-hot dispatch materializes a (g, t, E, C) tensor
    = g*k*cf elements PER TOKEN — at 384-expert/top-8 scale that is ~1.4
    TB/device (observed). Instead the routing is materialized as an integer
    slot map (g, E, C) built with a scatter, token vectors are *gathered*
    into expert slots, and the combine is top_k gathers from expert
    outputs. No tensor larger than (g, E, C, d) ever exists.

    Returns (y, aux_loss). Router in float32; Switch load-balancing loss.
    A packed (int mode) layer serves: it runs `moe_dropless`.
    """
    if cfg.qcfg.mode == "int":
        y, _ = moe_dropless(p, x, cfg)
        return y, jnp.float32(0.0)
    b, s, d = x.shape
    gs = min(cfg.group_size, b * s)
    tokens = x.reshape(-1, d)
    n_tok = tokens.shape[0]
    pad = (-n_tok) % gs
    if pad:
        tokens = jnp.pad(tokens, ((0, pad), (0, 0)))
    ng = tokens.shape[0] // gs
    # NOTE (refuted optimization, EXPERIMENTS.md §Perf): sharding groups
    # over data x model to turn the dispatch-gather backward into a
    # reduce-scatter made things dramatically worse (collective term
    # 56.9s -> 1085s at kimi train_4k) — GSPMD cannot partition a gather
    # whose indices live on a different axis layout and falls back to
    # replication. Tokens stay data-sharded / model-replicated.
    tokens = constrain(tokens.reshape(ng, gs, d), ("batch", None, None))

    gate_vals, expert_idx, probs = moe_route(p, tokens, cfg)  # (g,t,k)

    cap = cfg.capacity(gs)
    e = cfg.n_experts
    # position-in-expert via cumsum over the flattened (t,k) choice order
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)  # (g,t,k,e)
    flat = onehot.reshape(ng, gs * cfg.top_k, e)
    pos = jnp.cumsum(flat, axis=1) - 1                        # (g,t*k,e)
    pos = (pos * flat).sum(-1).reshape(ng, gs, cfg.top_k)     # (g,t,k)
    keep = pos < cap
    gate_vals = gate_vals * keep.astype(gate_vals.dtype)

    # ---- dispatch: scatter token ids into (g, E, C) slots, gather rows
    g_ar = jnp.arange(ng)[:, None, None]
    t_ar = jnp.broadcast_to(jnp.arange(gs)[None, :, None],
                            (ng, gs, cfg.top_k))
    pos_c = jnp.where(keep, pos, cap)  # cap == out-of-bounds -> dropped
    slot_tok = jnp.full((ng, e, cap), gs, jnp.int32)  # gs == padding row id
    slot_tok = slot_tok.at[
        jnp.broadcast_to(g_ar, (ng, gs, cfg.top_k)),
        expert_idx, pos_c].set(t_ar, mode="drop")
    tokens_pad = jnp.concatenate(
        [tokens, jnp.zeros((ng, 1, d), tokens.dtype)], axis=1)
    expert_in = jax.vmap(lambda tt, st: tt[st])(tokens_pad, slot_tok)
    expert_in = constrain(expert_in, ("batch", "experts", None, None))

    h = jnp.einsum("gecd,edf->gecf", expert_in, p["wi"].astype(x.dtype))
    g_ = jnp.einsum("gecd,edf->gecf", expert_in, p["wg"].astype(x.dtype))
    hidden = constrain(_act(h, g_, cfg.act),
                       ("batch", "experts", None, None))
    expert_out = constrain(
        jnp.einsum("gecf,efd->gecd", hidden, p["wo"].astype(x.dtype)),
        ("batch", "experts", None, None))

    # ---- combine: top_k gathers of (g, t, d) — never (g,t,E,C)
    flat_eo = expert_out.reshape(ng, e * cap, d)
    y = jnp.zeros((ng, gs, d), x.dtype)
    for kk in range(cfg.top_k):
        idx = expert_idx[:, :, kk] * cap + pos_c[:, :, kk]    # (g,t)
        idx = jnp.minimum(idx, e * cap - 1)
        gathered = jax.vmap(lambda eo, ix: eo[ix])(flat_eo, idx)
        w = (gate_vals[:, :, kk] * keep[:, :, kk]).astype(x.dtype)
        y = y + gathered * w[..., None]
    y = constrain(y, ("batch", None, None))
    y = y.reshape(-1, d)[:n_tok].reshape(b, s, d)

    if cfg.shared_expert:
        y = y + mlp_apply(p["shared"], x,
                          MlpConfig(cfg.d_model, cfg.d_ff, cfg.act, cfg.qcfg,
                                    cfg.plan, f"{cfg.path}/shared"))

    # Switch aux loss: e * sum_e(frac_tokens_e * frac_probs_e)
    frac_tok = jnp.mean(onehot[:, :, 0].astype(jnp.float32), axis=1)  # (g,e)
    frac_prob = jnp.mean(probs, axis=1)
    aux = e * jnp.mean(jnp.sum(frac_tok * frac_prob, axis=-1))
    return y, aux


def moe_route(p, x, cfg: MoeConfig):
    """Router in float32 over tokens x (..., d) -> (weights (..., k)
    float32, experts (..., k) int32, scores (..., E)).

    softmax: the top k of the softmax, as they are. sigmoid (LFM2):
    scores ``s = sigmoid(x W_r)``, the top k of ``s + expert_bias`` (the
    bias only chooses), weights ``s`` of those, renormalised over the k
    (+1e-6) when ``norm_topk``, times ``routed_scale``."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if cfg.router == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = jax.lax.top_k(probs, cfg.top_k)
        return weights, idx, probs
    if cfg.router != "sigmoid":
        raise ValueError(f"unknown router {cfg.router!r}")
    s = jax.nn.sigmoid(logits)
    choose = s + p["expert_bias"].astype(jnp.float32) if cfg.expert_bias \
        else s
    _, idx = jax.lax.top_k(choose, cfg.top_k)
    weights = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    return weights * cfg.routed_scale, idx, s


def _experts(p, x_e, cfg: MoeConfig, name: str, shared_input: bool):
    """One projection of every expert. x_e: (N, d_in) when every expert
    reads the same rows (``shared_input``), else (E, N, d_in); -> (E, N,
    d_out) in x_e's dtype."""
    w = p[name]
    if cfg.qcfg.mode != "int":
        eq = "nd,edf->enf" if shared_input else "end,edf->enf"
        return jnp.einsum(eq, x_e, w.astype(x_e.dtype))
    from repro.kernels.api import xla_grouped_int_gemm

    x_q, a_scale = quantize_activations(x_e, cfg.qcfg)
    if shared_input:
        x_q = jnp.broadcast_to(x_q, (cfg.n_experts,) + x_q.shape)
    return xla_grouped_int_gemm(x_q, w["w_packed"], w_bits=cfg.qcfg.w_bits,
                                scale=w["w_scale"] * a_scale,
                                out_dtype=x_e.dtype)


def moe_dropless(p, x, cfg: MoeConfig):
    """Every (token, expert) pair the router picks, none dropped. x: (B,
    S, d) -> (y (B, S, d), experts hit: int32, the number of experts that
    at least one token of x picked)."""
    b, s, d = x.shape
    tokens = x.reshape(-1, d)
    weights, idx, _ = moe_route(p, tokens, cfg)
    picked = jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32)
    comb = jnp.einsum("nk,nke->ne", weights, picked)          # (N, E)
    hits = jnp.sum(jnp.any(picked > 0, axis=(0, 1))).astype(jnp.int32)
    h = _experts(p, tokens, cfg, "wi", True)
    g = _experts(p, tokens, cfg, "wg", True)
    out = _experts(p, _act(h, g, cfg.act), cfg, "wo", False)  # (E, N, d)
    y = jnp.einsum("ne,end->nd", comb, out.astype(jnp.float32))
    y = y.astype(x.dtype).reshape(b, s, d)
    if cfg.shared_expert:
        y = y + mlp_apply(p["shared"], x,
                          MlpConfig(cfg.d_model, cfg.d_ff, cfg.act, cfg.qcfg,
                                    cfg.plan, f"{cfg.path}/shared"))
    return y, hits
