"""Grouped-query attention with causal/local/bidirectional masks, cross
attention, and an (optionally int8-quantized) KV cache for decode.

GQA is computed with an explicit group dim (no KV head replication is ever
materialized). All projections are quantization-aware Dense layers — the
paper's packed sub-byte GEMM applies to every projection here.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.deploy.policy import PrecisionPlan, resolve_qcfg
from repro.nn.layers import (QuantConfig, QOFF, dense_apply, dense_def,
                             norm_apply, norm_def, rope_apply, rope_single)
from repro.parallel.ctx import active_mesh, constrain, constrain_first

NEG_INF = -2.0e38


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    kv_heads: int
    head_dim: int
    qkv_bias: bool = False        # qwen2.5
    kv_quant_bits: int = 16       # 16 (bf16) | 8 (int8 cache)
    qcfg: QuantConfig = QOFF
    # mixed-precision deployment: per-projection override of qcfg resolved
    # by this block's param path + projection name (wq/wk/wv/wo)
    plan: Optional[PrecisionPlan] = None
    path: str = "layers/attn"
    # per-head RMSNorm on q and k before RoPE (LFM2's q/k_layernorm)
    qk_norm: bool = False
    norm_eps: float = 1e-6

    @property
    def groups(self):
        return self.n_heads // self.kv_heads

    def q(self, name: str) -> QuantConfig:
        return resolve_qcfg(self.plan, f"{self.path}/{name}", self.qcfg)


def attn_def(cfg: AttnConfig, dtype=jnp.float32):
    d, h, hk, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    p = {
        "wq": dense_def(d, h * dh, ("embed", "heads"), bias=cfg.qkv_bias,
                        qcfg=cfg.q("wq"), dtype=dtype),
        "wk": dense_def(d, hk * dh, ("embed", "kv_heads"), bias=cfg.qkv_bias,
                        qcfg=cfg.q("wk"), dtype=dtype),
        "wv": dense_def(d, hk * dh, ("embed", "kv_heads"), bias=cfg.qkv_bias,
                        qcfg=cfg.q("wv"), dtype=dtype),
        "wo": dense_def(h * dh, d, ("heads", "embed"), qcfg=cfg.q("wo"),
                        dtype=dtype),
    }
    if cfg.qk_norm:
        one = norm_def(dh, "rmsnorm", dtype)
        p["q_norm"] = {"scale": dataclasses.replace(one["scale"],
                                                     axes=(None,))}
        p["k_norm"] = dict(p["q_norm"])
    return p


def _qk_norm(p, q, k, cfg: AttnConfig):
    """Per-head RMSNorm over head_dim (identity unless cfg.qk_norm)."""
    if not cfg.qk_norm:
        return q, k
    return (norm_apply(p["q_norm"], q, "rmsnorm", cfg.norm_eps),
            norm_apply(p["k_norm"], k, "rmsnorm", cfg.norm_eps))


def _split_heads(x, n, dh):
    return x.reshape(*x.shape[:-1], n, dh)


def _mask_full(q_len, k_len, mode, window, q_offset=0):
    """(q_len, k_len) bool allow-mask. mode: causal|local|bidir.
    `window` may be a traced scalar (per-layer scanned value)."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(k_len)[None, :]
    if mode == "bidir":
        return jnp.ones((q_len, k_len), bool)
    allow = k_pos <= q_pos
    if mode == "local":
        allow = allow & (q_pos - k_pos < window)
    return allow


def attn_strategy(hk: int, groups: int, s_len: int, t_len: int,
                  batch=None) -> str:
    """One coherent sharding strategy per attention call (mixing per-tensor
    first-fit choices forces SPMD reshard copies of score-sized tensors):

    'tp'  — kv_heads divide the model axis: classic TP (Megatron).
    'gp'  — q-head groups divide: shard the GQA group dim (q-only TP).
    'cp'  — context parallel: shard q-seq (train/prefill) / kv-seq (decode),
            GSPMD emits partial-softmax psums (flash-decode style).
    """
    mesh = active_mesh()
    if mesh is None:
        return "none"
    m = mesh.shape.get("model", 1)
    if hk % m == 0:
        return "tp"
    # NOTE: a batch-parallel variant (batch over data x model for the
    # attention region) was tried for the few-kv-head case and REFUTED:
    # per-layer residual resharding across the model axis cost more than
    # the CP score handling it replaced (kimi train_4k: collective term
    # 56.9s -> 125.3s, compute 10.9s -> 46.8s; EXPERIMENTS.md §Perf).
    if (s_len > 1 and s_len % m == 0) or (s_len == 1 and t_len % m == 0):
        return "cp"
    if groups % m == 0:
        return "gp"
    return "none"


_SCORE_AXES = {  # (B, Hk, G, S, T)
    "tp": ("batch", "kv_heads", None, None, None),
    "gp": ("batch", None, "heads", None, None),
    "bp": ("batch_full", None, None, None, None),
}


def _sdpa(q, k, v, mask, strategy="none"):
    """q: (B,S,Hk,G,Dh), k/v: (B,T,Hk,Dh), mask broadcastable to
    (B,Hk,G,S,T). float32 softmax."""
    dh = q.shape[-1]
    s_len = q.shape[1]
    scores = jnp.einsum("bshgd,bthd->bhgst", q, k,
                        preferred_element_type=jnp.float32)
    if strategy in _SCORE_AXES:
        scores = constrain(scores, _SCORE_AXES[strategy])
    elif strategy == "cp":
        scores = constrain(scores, ("batch", None, None, "seq_model", None)
                           if s_len > 1 else
                           ("batch", None, None, None, "kv_seq"))
    scores = scores * (dh ** -0.5)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs.astype(v.dtype), v)
    return out


def _kv_store(x, bits):
    if bits == 8:
        scale = 8.0 / 127.0  # static symmetric grid for normalized k/v
        return jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                        -127, 127).astype(jnp.int8)
    return x


def _kv_load(x, bits, dtype):
    if bits == 8:
        return (x.astype(jnp.float32) * (8.0 / 127.0)).astype(dtype)
    return x


def attn_apply(p, x, cfg: AttnConfig, *, cos, sin, mode="causal",
               window=None, cross_kv=None):
    """Full-sequence attention (training / prefill).

    cross_kv: (k_src, v_src) pre-projected encoder K/V for cross-attention
    (mode must be 'bidir'; RoPE skipped).
    Returns (out, (k, v)) so callers can build decode caches from prefill.
    """
    b, s, _ = x.shape
    h, hk, dh, g = cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.groups
    q = _split_heads(dense_apply(p["wq"], x, qcfg=cfg.q("wq")), h, dh)
    t_len = x.shape[1] if cross_kv is None else cross_kv[0].shape[1]
    strat = attn_strategy(hk, g, s, t_len, batch=b)
    if cross_kv is None:
        k = _split_heads(dense_apply(p["wk"], x, qcfg=cfg.q("wk")), hk, dh)
        v = _split_heads(dense_apply(p["wv"], x, qcfg=cfg.q("wv")), hk, dh)
        kv_axes = {"tp": ("batch", None, "kv_heads", None),
                   "gp": ("batch", None, None, None),
                   "bp": ("batch_full", None, None, None),
                   "cp": ("batch", None, None, None)}.get(strat)
        if kv_axes:
            k = constrain(k, kv_axes)
            v = constrain(v, kv_axes)
        q, k = _qk_norm(p, q, k, cfg)
        q = rope_apply(q, cos, sin)
        k = rope_apply(k, cos, sin)
    else:
        k, v = cross_kv
    q = q.reshape(b, s, hk, g, dh)
    q_axes = {"tp": ("batch", None, "kv_heads", None, None),
              "gp": ("batch", None, None, "heads", None),
              "bp": ("batch_full", None, None, None, None),
              "cp": ("batch", "seq_model", None, None, None)}.get(strat)
    if q_axes:
        q = constrain(q, q_axes)
    t = k.shape[1]
    mask = _mask_full(s, t, mode, window)[None, None, None]
    out = _sdpa(q, k, v, mask, strat)
    out = out.reshape(b, s, h * dh)
    y = dense_apply(p["wo"], out, qcfg=cfg.q("wo"))
    return constrain(y, ("batch", None, None)), (k, v)


def cross_kv_project(p, enc_out, cfg: AttnConfig):
    """Project encoder states once; reused across decode steps."""
    hk, dh = cfg.kv_heads, cfg.head_dim
    k = _split_heads(dense_apply(p["wk"], enc_out, qcfg=cfg.q("wk")), hk, dh)
    v = _split_heads(dense_apply(p["wv"], enc_out, qcfg=cfg.q("wv")), hk, dh)
    return k, v


def init_cache(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    shape = (batch, max_len, cfg.kv_heads, cfg.head_dim)
    store_t = jnp.int8 if cfg.kv_quant_bits == 8 else dtype
    return {"k": jnp.zeros(shape, store_t), "v": jnp.zeros(shape, store_t)}


def init_layer_stack_cache(cfg: AttnConfig, layers: int, batch: int,
                           max_len: int, dtype=jnp.bfloat16):
    """Every layer's cache in one stack, heads merged into the minor dim:
    (layers, batch, max_len, kv_heads * head_dim). Merged, a head_dim
    under 128 does not pad the TPU's (8, 128) tiles."""
    shape = (layers, batch, max_len, cfg.kv_heads * cfg.head_dim)
    store_t = jnp.int8 if cfg.kv_quant_bits == 8 else dtype
    return {"k": jnp.zeros(shape, store_t), "v": jnp.zeros(shape, store_t)}


def store_rows(kv, layer, index, rows):
    """``kv`` with ``rows`` (k, v), each (B, Hk*Dh), written at position
    ``index`` (B,) of ``layer``: in place when ``kv`` is not needed after."""
    r = jnp.arange(index.shape[0])
    return {"k": kv["k"].at[layer, r, index].set(rows[0]),
            "v": kv["v"].at[layer, r, index].set(rows[1])}


def attn_decode_stacked(p, x, kv, index, cfg: AttnConfig, layer, *,
                        theta=10000.0):
    """One-token causal decode against ``layer`` (an int or a traced
    index) of a stacked cache (`init_layer_stack_cache`) that it only
    reads. x: (B,1,d); index: (B,) true positions. The token attends to
    its slot's cached positions before ``index`` and to itself; the
    arithmetic is `attn_decode`'s (the new K/V rounded to the cache's
    type, a float32 softmax).

    Returns (out, rows): ``rows`` are the (k, v) entries, (B, Hk*Dh),
    that the caller stores at ``index`` (`store_rows`) once the layer
    has read the stack.
    """
    b = x.shape[0]
    h, hk, dh, g = cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.groups
    bits = cfg.kv_quant_bits
    q = _split_heads(dense_apply(p["wq"], x, qcfg=cfg.q("wq")), h, dh)
    k_new = _split_heads(dense_apply(p["wk"], x, qcfg=cfg.q("wk")), hk, dh)
    v_new = _split_heads(dense_apply(p["wv"], x, qcfg=cfg.q("wv")), hk, dh)
    q, k_new = _qk_norm(p, q, k_new, cfg)
    q = rope_single(q, index, theta).reshape(b, 1, hk, g, dh)
    kq = _kv_store(rope_single(k_new, index, theta), bits)
    vq = _kv_store(v_new, bits)
    t = kv["k"].shape[2]
    k = _kv_load(kv["k"][layer].reshape(b, t, hk, dh), bits, x.dtype)
    v = _kv_load(kv["v"][layer].reshape(b, t, hk, dh), bits, x.dtype)
    kn, vn = _kv_load(kq, bits, x.dtype), _kv_load(vq, bits, x.dtype)
    scale = dh ** -0.5
    sc = jnp.einsum("bshgd,bthd->bhgst", q, k,
                    preferred_element_type=jnp.float32) * scale
    allow = jnp.arange(t)[None, :] < index[:, None]            # (B, T)
    sc = jnp.where(allow[:, None, None, None, :], sc, NEG_INF)
    sn = jnp.einsum("bshgd,bthd->bhgst", q, kn,
                    preferred_element_type=jnp.float32) * scale
    top = jnp.maximum(jnp.max(sc, axis=-1, keepdims=True), sn)
    pc, pn = jnp.exp(sc - top), jnp.exp(sn - top)
    den = jnp.sum(pc, axis=-1, keepdims=True) + pn
    out = (jnp.einsum("bhgst,bthd->bshgd", (pc / den).astype(v.dtype), v)
           + jnp.einsum("bhgst,bthd->bshgd", (pn / den).astype(vn.dtype),
                        vn))
    y = dense_apply(p["wo"], out.reshape(b, 1, h * dh), qcfg=cfg.q("wo"))
    return y, (kq.reshape(b, -1), vq.reshape(b, -1))


def attn_decode(p, x, cache, index, cfg: AttnConfig, *, theta=10000.0,
                mode="causal", window=None, cross_kv=None,
                ring: bool = False):
    """One-token decode. x: (B,1,d); index: the TRUE position — a scalar
    int32 (wave decode: every row at the same step) or a (B,) int32
    vector (continuous batching: each slot at its own position);
    cache: dict(k,v) of (B,T,Hk,Dh). Returns (out, new_cache).

    The per-slot (vector) form runs the same per-element math as the
    scalar form — RoPE phases, cache writes, and masks are all computed
    row-wise — so an all-equal position vector is bit-exact vs the
    scalar path (the serve runtime's parity invariant).

    ring=True treats the cache as a ring buffer of T=window slots (local
    attention): slot = index % T, each slot j holds true position
    index - ((index - j) mod T); RoPE always uses true positions so the
    relative phases stay exact across wraps.
    """
    b = x.shape[0]
    h, hk, dh, g = cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.groups
    index = jnp.asarray(index)
    per_slot = index.ndim == 1            # (B,) per-slot positions
    q = _split_heads(dense_apply(p["wq"], x, qcfg=cfg.q("wq")), h, dh)
    if cross_kv is None:
        k_new = _split_heads(dense_apply(p["wk"], x, qcfg=cfg.q("wk")), hk, dh)
        v_new = _split_heads(dense_apply(p["wv"], x, qcfg=cfg.q("wv")), hk, dh)
        q, k_new = _qk_norm(p, q, k_new, cfg)
        q = rope_single(q, index, theta)
        k_new = rope_single(k_new, index, theta)
        kq = _kv_store(k_new, cfg.kv_quant_bits)
        vq = _kv_store(v_new, cfg.kv_quant_bits)
        t = cache["k"].shape[1]
        slot = (index % t) if ring else index
        if per_slot:
            # one write position per row; values are unchanged, only the
            # write address is batched, so bit-exactness is preserved
            upd = jax.vmap(lambda c, u, s:
                           jax.lax.dynamic_update_slice_in_dim(c, u, s,
                                                               axis=0))
            cache = {"k": upd(cache["k"], kq, slot),
                     "v": upd(cache["v"], vq, slot)}
        else:
            cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], kq,
                                                         slot, axis=1),
                "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], vq,
                                                         slot, axis=1),
            }
        k = _kv_load(cache["k"], cfg.kv_quant_bits, x.dtype)
        v = _kv_load(cache["v"], cfg.kv_quant_bits, x.dtype)
        k_pos = jnp.arange(t)[None, :]
        idx = index[:, None] if per_slot else index  # (B,1) | scalar
        if ring:
            true_pos = idx - ((idx - k_pos) % t)
            allow = true_pos >= 0
            if window is not None:
                allow = allow & (idx - true_pos < window)
        else:
            allow = k_pos <= idx
            if mode == "local":
                allow = allow & (idx - k_pos < window)
    else:
        k, v = cross_kv
        t = k.shape[1]
        allow = jnp.ones((1, t), bool)
    q = q.reshape(b, 1, hk, g, dh)
    strat = attn_strategy(hk, g, 1, t)
    mask = allow[:, None, None, None, :]  # (B,1,1,1,T) / (1,...)
    out = _sdpa(q, k, v, mask, strat)
    out = out.reshape(b, 1, h * dh)
    return dense_apply(p["wo"], out, qcfg=cfg.q("wo")), cache
