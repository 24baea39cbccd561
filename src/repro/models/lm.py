"""Unified decoder LM covering the dense / MoE / vision-cross-attn archs.

Layers are scanned (jax.lax.scan over stacked params) so HLO size is
depth-independent. Pattern-scheduled attention (gemma3's 5 local : 1 global)
is handled with *uniform* layer structure + per-layer scanned scalars
(window size, rope-table selector), so a single scan covers the whole stack.
Vision archs group the stack as [cross_every self-layers + 1 cross-layer]
per scan step.

Hybrid stacks (``cfg.layer_types``, LFM2) name each layer's operator, a
gated short conv or GQA, in an order that has no period, and follow the
first ``n_dense_layers`` operators with a dense SwiGLU and the rest with
MoE. They keep per-kind stacks (``conv_layers``, ``attn_layers``,
``dense_ffn``, ``moe_ffn``) visited in layer order, a KV cache for the
attention layers only and a rolling conv state for the conv layers.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.nn.attention import (AttnConfig, attn_apply, attn_decode,
                                attn_decode_stacked, attn_def,
                                cross_kv_project, init_cache,
                                init_layer_stack_cache, store_rows)
from repro.nn.layers import (dense_apply, dense_def, embedding_apply,
                             embedding_def, embedding_logits, norm_apply,
                             norm_def, rope_tables)
from repro.nn.mlp import (MlpConfig, MoeConfig, mlp_apply, mlp_def, moe_apply,
                          moe_def, moe_dropless)
from repro.nn.module import stack_defs
from repro.nn.shortconv import (ShortConvConfig, shortconv_apply,
                                shortconv_decode, shortconv_def,
                                shortconv_init_state)
from repro.parallel.ctx import constrain


def _attn_cfg(cfg: ModelConfig, path: str = "layers/attn") -> AttnConfig:
    """`path` locates this block in the param tree so the mixed-precision
    plan (cfg.quant_plan) can resolve per-projection bit-widths."""
    return AttnConfig(cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim_,
                      qkv_bias=cfg.qkv_bias, kv_quant_bits=cfg.kv_quant_bits,
                      qcfg=cfg.quant, plan=cfg.quant_plan, path=path,
                      qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps)


def _mlp_cfg(cfg: ModelConfig, path: str = "layers/mlp") -> MlpConfig:
    return MlpConfig(cfg.d_model, cfg.d_ff, cfg.act, cfg.quant,
                     cfg.quant_plan, path)


def _moe_cfg(cfg: ModelConfig, path: str = "layers/moe") -> MoeConfig:
    m = cfg.moe
    return MoeConfig(cfg.d_model, m.d_ff, m.n_experts, m.top_k,
                     m.capacity_factor, m.group_size, m.shared_expert,
                     cfg.act, cfg.quant, cfg.quant_plan, path, m.router,
                     m.expert_bias, m.norm_topk, m.routed_scale)


def _layer_def(cfg: ModelConfig, dtype):
    p = {"ln1": norm_def(cfg.d_model, cfg.norm, dtype),
         "attn": attn_def(_attn_cfg(cfg), dtype),
         "ln2": norm_def(cfg.d_model, cfg.norm, dtype)}
    if cfg.moe is not None:
        p["moe"] = moe_def(_moe_cfg(cfg), dtype)
    else:
        p["mlp"] = mlp_def(_mlp_cfg(cfg), dtype)
    return p


def _cross_layer_def(cfg: ModelConfig, dtype):
    return {"ln1": norm_def(cfg.d_model, cfg.norm, dtype),
            "xattn": attn_def(_attn_cfg(cfg, "cross_layers/xattn"), dtype),
            "ln2": norm_def(cfg.d_model, cfg.norm, dtype),
            "mlp": mlp_def(_mlp_cfg(cfg, "cross_layers/mlp"), dtype)}


def lm_def(cfg: ModelConfig, dtype=jnp.float32):
    if cfg.layer_types:
        return _hybrid_def(cfg, dtype)
    n_self, n_cross = _layer_split(cfg)
    p = {"embed": embedding_def(cfg.vocab, cfg.d_model, dtype),
         "layers": stack_defs(_layer_def(cfg, dtype), n_self),
         "final_norm": norm_def(cfg.d_model, cfg.norm, dtype)}
    if n_cross:
        p["cross_layers"] = stack_defs(_cross_layer_def(cfg, dtype), n_cross)
    if not cfg.tie_embeddings:
        from repro.nn.layers import padded_vocab
        p["head"] = dense_def(cfg.d_model, padded_vocab(cfg.vocab),
                              ("embed", "vocab"), dtype=dtype)
    return p


def _layer_split(cfg: ModelConfig):
    if cfg.cross_every:
        n_cross = cfg.n_layers // (cfg.cross_every + 1)
        return cfg.n_layers - n_cross, n_cross
    return cfg.n_layers, 0


def _layer_schedule(cfg: ModelConfig, seq_len: int):
    """Per-layer (window, rope_select) scanned arrays.

    window: effective attention window per layer (global -> seq_len).
    rope_select: 1 where the layer uses the local rope table.
    """
    kinds = cfg.layer_kinds()
    win = jnp.array([cfg.window if k == "local" else max(seq_len, 1)
                     for k in kinds], jnp.int32)
    rsel = jnp.array([1 if (k == "local" and cfg.rope_theta_local) else 0
                      for k in kinds], jnp.int32)
    return win, rsel


def _ropes(cfg: ModelConfig, seq_len: int, dtype):
    cos_g, sin_g = rope_tables(seq_len, cfg.head_dim_, cfg.rope_theta, dtype)
    if cfg.rope_theta_local:
        cos_l, sin_l = rope_tables(seq_len, cfg.head_dim_,
                                   cfg.rope_theta_local, dtype)
    else:
        cos_l, sin_l = cos_g, sin_g
    return (cos_g, sin_g), (cos_l, sin_l)


def _block(cfg, lp, x, cos, sin, window, collect_kv):
    """One decoder block (pre-norm). Returns (x, aux, kv).

    mode="local": window is a per-layer scanned value; global layers carry
    window == seq_len, so one uniform mask covers pattern schedules."""
    h, kv = attn_apply(lp["attn"], norm_apply(lp.get("ln1", {}), x, cfg.norm),
                       _attn_cfg(cfg), cos=cos, sin=sin, mode="local",
                       window=window)
    x = x + h
    aux = 0.0
    if cfg.moe is not None:
        h, aux = moe_apply(lp["moe"], norm_apply(lp.get("ln2", {}), x, cfg.norm),
                           _moe_cfg(cfg))
    else:
        h = mlp_apply(lp["mlp"], norm_apply(lp.get("ln2", {}), x, cfg.norm),
                      _mlp_cfg(cfg))
    x = x + h
    return x, aux, (kv if collect_kv else None)


def _cross_block(cfg, lp, x, src_kv):
    h, _ = attn_apply(lp["xattn"], norm_apply(lp.get("ln1", {}), x, cfg.norm),
                      _attn_cfg(cfg, "cross_layers/xattn"), cos=None, sin=None,
                      mode="bidir", cross_kv=src_kv)
    x = x + h
    x = x + mlp_apply(lp["mlp"], norm_apply(lp.get("ln2", {}), x, cfg.norm),
                      _mlp_cfg(cfg, "cross_layers/mlp"))
    return x


def forward(params, tokens, cfg: ModelConfig, *, src_embed=None,
            collect_kv: bool = False):
    """Training/prefill forward. tokens (B,S) -> logits (B,S,V).

    src_embed: (B, S_src, d) modality-frontend stub output for vision archs.
    Returns (logits, aux_loss, kv_stack or None).
    """
    if cfg.layer_types:
        return _hybrid_forward(params, tokens, cfg, collect_kv)
    dtype = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    b, s = tokens.shape
    x = constrain(embedding_apply(params["embed"], tokens).astype(dtype),
                  ("batch", None, None))
    if cfg.scale_embed:
        x = x * (cfg.d_model ** 0.5)
    (cg, sg), (cl, sl) = _ropes(cfg, s, dtype)
    win, rsel = _layer_schedule(cfg, s)

    n_self, n_cross = _layer_split(cfg)
    acfg = _attn_cfg(cfg, "cross_layers/xattn")  # only used for cross K/V

    if n_cross == 0:
        def body(carry, per_layer):
            x, aux = carry
            lp, w_l, r_l = per_layer
            cos = jnp.where(r_l == 1, cl, cg)
            sin = jnp.where(r_l == 1, sl, sg)
            x, a, kv = _block(cfg, lp, x, cos, sin, w_l, collect_kv)
            return (x, aux + a), kv

        body = jax.checkpoint(body) if cfg.remat else body
        (x, aux), kvs = jax.lax.scan(
            body, (x, jnp.float32(0.0)), (params["layers"], win, rsel))
    else:
        # grouped scan: cross_every self layers then one cross layer
        assert src_embed is not None, f"{cfg.name} needs src_embed input"
        src = src_embed.astype(dtype)
        ce = cfg.cross_every
        n_groups = n_cross
        grouped = jax.tree.map(
            lambda a: a.reshape(n_groups, ce, *a.shape[1:]),
            params["layers"])

        def group_body(carry, per_group):
            x, aux = carry
            gp, xp, w_g, r_g = per_group

            def inner(c2, pl2):
                x2, aux2 = c2
                lp, w_l, r_l = pl2
                cos = jnp.where(r_l == 1, cl, cg)
                sin = jnp.where(r_l == 1, sl, sg)
                x2, a2, _ = _block(cfg, lp, x2, cos, sin, w_l, False)
                return (x2, aux2 + a2), None

            (x, aux), _ = jax.lax.scan(inner, (x, aux), (gp, w_g, r_g))
            src_kv = cross_kv_project(xp["xattn"], src, acfg)
            x = _cross_block(cfg, xp, x, src_kv)
            return (x, aux), None

        group_body = jax.checkpoint(group_body) if cfg.remat else group_body
        win_g = win[:n_self].reshape(n_groups, ce)
        rsel_g = rsel[:n_self].reshape(n_groups, ce)
        (x, aux), _ = jax.lax.scan(
            group_body, (x, jnp.float32(0.0)),
            (grouped, params["cross_layers"], win_g, rsel_g))
        kvs = None

    x = norm_apply(params.get("final_norm", {}), x, cfg.norm)
    logits = _logits(params, x, cfg)
    return logits, aux, kvs


def _logits(params, x, cfg):
    if cfg.tie_embeddings:
        lg = embedding_logits(params["embed"], x, cfg.vocab)
    else:
        lg = dense_apply(params["head"], x)
        vp = lg.shape[-1]
        if vp != cfg.vocab:
            mask = (jnp.arange(vp) < cfg.vocab)
            lg = jnp.where(mask, lg, jnp.asarray(-1e9, lg.dtype))
    return constrain(lg, ("batch", None, "vocab"))


# ------------------------------------------------------------- serving ---

def lm_init_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16):
    if cfg.layer_types:
        return _hybrid_init_cache(cfg, batch, max_len, dtype)
    n_self, n_cross = _layer_split(cfg)
    acfg = _attn_cfg(cfg)
    one = init_cache(acfg, batch, max_len, dtype)
    cache = {"kv": jax.tree.map(
        lambda a: jnp.zeros((n_self,) + a.shape, a.dtype), one)}
    if n_cross:
        dh, hk = acfg.head_dim, acfg.kv_heads
        cache["cross_kv"] = jnp.zeros(
            (n_cross, 2, batch, cfg.src_len, hk, dh), dtype)
    return cache


def decode_step(params, cache, token, index, cfg: ModelConfig, *,
                src_embed=None):
    """One decode step. token (B,1) int32; index scalar int32.

    For vision archs the cross K/V are recomputed from src_embed on step 0
    and cached (prefill fills them in practice; dry-run lowers this path).
    Returns (logits (B,1,V), new_cache); a hybrid stack with MoE layers
    adds a third item, the experts each MoE layer's tokens hit
    ((experts_hit_layers(cfg),) int32). The named scopes ``attn``,
    ``mlp`` (per scanned layer; ``conv`` and ``moe`` in hybrid stacks)
    and ``head`` label the device ops.
    """
    if cfg.layer_types:
        return _hybrid_decode(params, cache, token, index, cfg)
    dtype = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    b = token.shape[0]
    max_len = cache["kv"]["k"].shape[2]
    x = embedding_apply(params["embed"], token).astype(dtype)
    if cfg.scale_embed:
        x = x * (cfg.d_model ** 0.5)
    th_g = jnp.float32(cfg.rope_theta)
    th_l = jnp.float32(cfg.rope_theta_local or cfg.rope_theta)
    win, rsel = _layer_schedule(cfg, max_len)
    n_self, n_cross = _layer_split(cfg)
    acfg = _attn_cfg(cfg)
    acfg_x = _attn_cfg(cfg, "cross_layers/xattn")

    if n_cross == 0:
        def body(x, per_layer):
            lp, kv_l, w_l, r_l = per_layer
            th = jnp.where(r_l == 1, th_l, th_g)
            with jax.named_scope("attn"):
                h, new_kv = attn_decode(
                    lp["attn"], norm_apply(lp.get("ln1", {}), x, cfg.norm),
                    kv_l, index, acfg, theta=th, mode="local", window=w_l)
            x = x + h
            with jax.named_scope("mlp"):
                if cfg.moe is not None:
                    h, _ = moe_apply(lp["moe"],
                                     norm_apply(lp.get("ln2", {}), x, cfg.norm),
                                     _moe_cfg(cfg))
                else:
                    h = mlp_apply(lp["mlp"],
                                  norm_apply(lp.get("ln2", {}), x, cfg.norm),
                                  _mlp_cfg(cfg))
            return x + h, new_kv

        x, new_kv = jax.lax.scan(body, x, (params["layers"],
                                           cache["kv"], win, rsel))
        new_cache = dict(cache, kv=new_kv)
    else:
        ce = cfg.cross_every
        n_groups = n_cross
        grouped = jax.tree.map(
            lambda a: a.reshape(n_groups, ce, *a.shape[1:]),
            params["layers"])
        kv_grouped = jax.tree.map(
            lambda a: a.reshape(n_groups, ce, *a.shape[1:]), cache["kv"])
        win_g = win[:n_self].reshape(n_groups, ce)
        rsel_g = rsel[:n_self].reshape(n_groups, ce)

        def group_body(x, per_group):
            gp, xp, kvg, xkv, w_g, r_g = per_group

            def inner(x2, pl2):
                lp, kv_l, w_l, r_l = pl2
                th = jnp.where(r_l == 1, th_l, th_g)
                with jax.named_scope("attn"):
                    h, nkv = attn_decode(
                        lp["attn"], norm_apply(lp.get("ln1", {}), x2, cfg.norm),
                        kv_l, index, acfg, theta=th, mode="local", window=w_l)
                x2 = x2 + h
                with jax.named_scope("mlp"):
                    h = mlp_apply(lp["mlp"],
                                  norm_apply(lp.get("ln2", {}), x2, cfg.norm),
                                  _mlp_cfg(cfg))
                return x2 + h, nkv

            x, nkvg = jax.lax.scan(inner, x, (gp, kvg, w_g, r_g))
            h, _ = attn_decode(
                xp["xattn"], norm_apply(xp.get("ln1", {}), x, cfg.norm), None, index,
                acfg_x, mode="bidir", cross_kv=(xkv[0], xkv[1]))
            x = x + h
            x = x + mlp_apply(xp["mlp"], norm_apply(xp.get("ln2", {}), x, cfg.norm),
                              _mlp_cfg(cfg, "cross_layers/mlp"))
            return x, nkvg

        x, new_kvg = jax.lax.scan(
            group_body, x,
            (grouped, params["cross_layers"], kv_grouped,
             cache["cross_kv"], win_g, rsel_g))
        new_kv = jax.tree.map(
            lambda a: a.reshape(n_self, *a.shape[2:]), new_kvg)
        new_cache = dict(cache, kv=new_kv)

    with jax.named_scope("head"):
        x = norm_apply(params.get("final_norm", {}), x, cfg.norm)
        return _logits(params, x, cfg), new_cache


# ------------------------------------------------------- hybrid stacks ---

HYBRID_OPS = ("conv", "full_attention")


def _hybrid_layout(cfg: ModelConfig):
    """Per layer (operator, its index in its stack, ffn kind, its index),
    and the size of each stack."""
    n = dict.fromkeys(HYBRID_OPS + ("mlp", "moe"), 0)
    out = []
    for i, op in enumerate(cfg.layer_types):
        if op not in HYBRID_OPS:
            raise ValueError(f"{cfg.name}: layer {i} is {op!r}, not one of "
                             f"{HYBRID_OPS}")
        ffn = "moe" if cfg.moe is not None and i >= cfg.n_dense_layers \
            else "mlp"
        out.append((op, n[op], ffn, n[ffn]))
        n[op] += 1
        n[ffn] += 1
    return out, n


def experts_hit_layers(cfg: ModelConfig) -> int:
    """MoE layers whose experts hit a hybrid decode step returns (0 when
    it returns none, and only (logits, cache))."""
    if cfg.family != "lm" or not cfg.layer_types:
        return 0
    return _hybrid_layout(cfg)[1]["moe"]


def _conv_cfg(cfg: ModelConfig) -> ShortConvConfig:
    return ShortConvConfig(cfg.d_model, cfg.d_conv, cfg.quant,
                           cfg.quant_plan, "conv_layers/conv")


_HYBRID_ATTN = "attn_layers/attn"
_HYBRID_MLP = "dense_ffn/mlp"
_HYBRID_MOE = "moe_ffn/moe"


def _hybrid_def(cfg: ModelConfig, dtype):
    _, n = _hybrid_layout(cfg)
    d = cfg.d_model
    stacks = {
        "conv_layers": ("conv", {"ln1": norm_def(d, cfg.norm, dtype),
                                 "conv": shortconv_def(_conv_cfg(cfg), dtype)}),
        "attn_layers": ("full_attention", {
            "ln1": norm_def(d, cfg.norm, dtype),
            "attn": attn_def(_attn_cfg(cfg, _HYBRID_ATTN), dtype)}),
        "dense_ffn": ("mlp", {"ln2": norm_def(d, cfg.norm, dtype),
                              "mlp": mlp_def(_mlp_cfg(cfg, _HYBRID_MLP),
                                             dtype)}),
    }
    if cfg.moe is not None:
        stacks["moe_ffn"] = ("moe", {
            "ln2": norm_def(d, cfg.norm, dtype),
            "moe": moe_def(_moe_cfg(cfg, _HYBRID_MOE), dtype)})
    p = {"embed": embedding_def(cfg.vocab, d, dtype),
         "final_norm": norm_def(d, cfg.norm, dtype)}
    for name, (kind, layer) in stacks.items():
        if n[kind]:
            p[name] = stack_defs(layer, n[kind])
    if not cfg.tie_embeddings:
        from repro.nn.layers import padded_vocab
        p["head"] = dense_def(d, padded_vocab(cfg.vocab), ("embed", "vocab"),
                              dtype=dtype)
    return p


def _at(stack, i):
    """Layer i (a Python int or a traced index) of a stack."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)


def _norm(cfg: ModelConfig, p, x):
    return norm_apply(p, x, cfg.norm, cfg.norm_eps)


def _hybrid_ffn(params, x, cfg: ModelConfig, kind: str, j: int):
    """x + ffn(ffn_norm(x)) -> (x, experts hit or None)."""
    if kind == "mlp":
        lp = _at(params["dense_ffn"], j)
        with jax.named_scope("mlp"):
            return x + mlp_apply(lp["mlp"], _norm(cfg, lp["ln2"], x),
                                 _mlp_cfg(cfg, _HYBRID_MLP)), None
    lp = _at(params["moe_ffn"], j)
    with jax.named_scope("moe"):
        y, hit = moe_dropless(lp["moe"], _norm(cfg, lp["ln2"], x),
                              _moe_cfg(cfg, _HYBRID_MOE))
    return x + y, hit


def _hybrid_forward(params, tokens, cfg: ModelConfig, collect_kv: bool):
    dtype = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    x = embedding_apply(params["embed"], tokens).astype(dtype)
    cos, sin = rope_tables(tokens.shape[1], cfg.head_dim_, cfg.rope_theta,
                           dtype)
    acfg, ccfg = _attn_cfg(cfg, _HYBRID_ATTN), _conv_cfg(cfg)
    kvs = []
    for op, i, ffn, j in _hybrid_layout(cfg)[0]:
        if op == "conv":
            lp = _at(params["conv_layers"], i)
            x = x + shortconv_apply(lp["conv"], _norm(cfg, lp["ln1"], x),
                                    ccfg)
        else:
            lp = _at(params["attn_layers"], i)
            h, kv = attn_apply(lp["attn"], _norm(cfg, lp["ln1"], x), acfg,
                               cos=cos, sin=sin)
            x = x + h
            kvs.append(kv)
        x, _ = _hybrid_ffn(params, x, cfg, ffn, j)
    x = _norm(cfg, params["final_norm"], x)
    kv_stack = (jax.tree.map(lambda *a: jnp.stack(a), *kvs)
                if collect_kv and kvs else None)
    return _logits(params, x, cfg), jnp.float32(0.0), kv_stack


def _hybrid_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    _, n = _hybrid_layout(cfg)
    cache = {}
    if n["full_attention"]:
        cache["kv"] = init_layer_stack_cache(
            _attn_cfg(cfg, _HYBRID_ATTN), n["full_attention"], batch,
            max_len, dtype)
    if n["conv"]:
        one = shortconv_init_state(_conv_cfg(cfg), batch, dtype)
        cache["conv"] = jnp.zeros((n["conv"],) + one.shape, one.dtype)
    return cache


def _runs(layout):
    """Consecutive layers of one (operator, ffn) kind: [(op, ffn,
    [(operator index, ffn index), ...]), ...] in layer order."""
    runs = []
    for op, i, ffn, j in layout:
        if runs and runs[-1][:2] == (op, ffn):
            runs[-1][2].append((i, j))
        else:
            runs.append((op, ffn, [(i, j)]))
    return runs


def _hybrid_decode(params, cache, token, index, cfg: ModelConfig):
    """Each run of consecutive layers of one kind (LFM2: a lone
    attention layer, then two or three conv layers, each with its MoE)
    is one scan over indices into the per-kind stacks, so XLA unpacks
    one layer's packed experts at a time; a run of one layer runs
    unrolled. A run carries only its own state: the conv runs the conv
    rows, the attention runs the KV stack, which each layer updates in
    place (`store_rows`) after reading it."""
    dtype = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    x = embedding_apply(params["embed"], token).astype(dtype)
    acfg, ccfg = _attn_cfg(cfg, _HYBRID_ATTN), _conv_cfg(cfg)
    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32), token.shape[:1])
    kv, conv = cache.get("kv"), cache.get("conv")

    def layer(x, state, op, ffn, i, j):
        """Operator ``op`` of stack index i and ffn j (ints or traced);
        ``state`` is the conv rows or the KV stack -> (x, state, hit)."""
        if op == "conv":
            lp = _at(params["conv_layers"], i)
            with jax.named_scope("conv"):
                h, st = shortconv_decode(lp["conv"],
                                         _norm(cfg, lp["ln1"], x), state[i],
                                         ccfg)
            state = state.at[i].set(st)
        else:
            lp = _at(params["attn_layers"], i)
            with jax.named_scope("attn"):
                h, rows = attn_decode_stacked(
                    lp["attn"], _norm(cfg, lp["ln1"], x), state, index, acfg,
                    i, theta=cfg.rope_theta)
            state = store_rows(state, i, index, rows)
        x, hit = _hybrid_ffn(params, x + h, cfg, ffn, j)
        return x, state, hit

    hits = []
    for op, ffn, ij in _runs(_hybrid_layout(cfg)[0]):
        state = conv if op == "conv" else kv
        if len(ij) == 1:
            x, state, hit = layer(x, state, op, ffn, *ij[0])
            hit = None if hit is None else hit[None]
        else:
            def body(carry, ij_t, op=op, ffn=ffn):
                x, state, hit = layer(*carry, op, ffn, ij_t[0], ij_t[1])
                return (x, state), hit

            (x, state), hit = jax.lax.scan(body, (x, state),
                                           jnp.array(ij, jnp.int32))
        if hit is not None:
            hits.append(hit)
        if op == "conv":
            conv = state
        else:
            kv = state
    new_cache = dict(cache)
    if kv is not None:
        new_cache["kv"] = kv
    if conv is not None:
        new_cache["conv"] = conv
    with jax.named_scope("head"):
        logits = _logits(params, _norm(cfg, params["final_norm"], x), cfg)
    if not hits:
        return logits, new_cache
    return logits, new_cache, jnp.concatenate(hits)
